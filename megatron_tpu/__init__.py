"""megatron_tpu: TPU-native Megatron-capability LLM training framework."""
