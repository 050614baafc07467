#!/bin/bash
# 32k long-context training: flash kernel + RoPE scaling + full remat +
# context parallelism (BASELINE config 5; not measured on the chip:
# ROADMAP R7).
DATA=${DATA:-data/corpus}

python finetune.py \
    --model llama2-7b --seq_length 32768 --rope_scaling_factor 8.0 \
    --use_flash_attn --recompute_granularity full \
    --context_parallel_size 4 --context_parallel_algo ring \
    --bf16 --use_distributed_optimizer \
    --data_path "$DATA" \
    --train_iters 1000 --global_batch_size 32 --micro_batch_size 1 \
    --lr 1e-5 --save ckpts/llama2-32k
