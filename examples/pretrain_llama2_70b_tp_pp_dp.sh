#!/bin/bash
# Llama-2-70B (GQA) on a v5p-128 slice: TP=8 x PP=4 x DP=4 — BASELINE
# config 4. Not run on a chip: no cell of BENCHMARK.json has this shape.
# ZeRO-1 (--use_distributed_optimizer) dp-shards the Adam state; under
# pp the embedding's and head's moments stay replicated, 403 MB a device
# at this shape (docs/parallelism.md "The partitioner's CHECK under
# pp > 1"). vpp keeps the reference's interleaved checkpoint
# layout under the 1F1B memory bound if you need layout parity:
# add --num_layers_per_virtual_pipeline_stage 10 (80 layers / pp4 / 2).
# Prereqs: converted weights (tools/convert_hf_checkpoint.py --model
# llama2-70b) and a preprocessed .bin/.idx corpus. Launch once per host
# under multi-host (parallel/multihost.py picks up the JAX coordinator
# env; all hosts run the identical command).

CKPT=${CKPT:-ckpts/llama2-70b}
DATA=${DATA:-data/corpus}
SAVE=${SAVE:-ckpts/llama2-70b-pt}

python finetune.py \
    --model llama2-70b \
    --load "$CKPT" --finetune \
    --tensor_model_parallel_size 8 \
    --pipeline_model_parallel_size 4 \
    --sequence_parallel \
    --use_distributed_optimizer \
    --bf16 --recompute_granularity selective \
    --data_path "$DATA" --split 989,10,1 \
    --train_iters 1000 --global_batch_size 1024 --micro_batch_size 1 \
    --lr 1.5e-4 --lr_decay_style cosine --lr_warmup_iters 100 \
    --adam_beta1 0.9 --adam_beta2 0.95 \
    --weight_decay 0.1 --clip_grad 1.0 \
    --log_interval 1 --save_interval 200 --eval_interval 200 \
    --save "$SAVE" --tensorboard_dir runs/llama2-70b
