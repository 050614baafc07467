#!/bin/bash
# Text-generation REST server + CLI client
# (ref: examples/run_text_generation_server_345M.sh).
#
# The server runs the continuous-batching engine by default
# (megatron_tpu/serving): NUM_SLOTS concurrent decode slots over a
# pooled KV cache, bounded admission queue, 429 backpressure.
# SERIAL=1 restores the reference's one-lock serial path.
set -e
CKPT=${CKPT:-ckpts/llama2-7b-ft}
TOK=${TOK:-meta-llama/Llama-2-7b-hf}
PORT=${PORT:-5000}
NUM_SLOTS=${NUM_SLOTS:-8}
MAX_QUEUE=${MAX_QUEUE:-64}

EXTRA=()
[ -n "$SERIAL" ] && EXTRA+=(--serial)

python tools/run_text_generation_server.py \
    --load "$CKPT" --tokenizer_type HFTokenizer --tokenizer_model "$TOK" \
    --port "$PORT" --num_slots "$NUM_SLOTS" --max_queue "$MAX_QUEUE" \
    "${EXTRA[@]}" &
SERVER_PID=$!
trap 'kill $SERVER_PID 2>/dev/null' EXIT

# wait for the server (checkpoint load + first compile can take minutes)
for _ in $(seq 1 120); do
    if curl -s -o /dev/null "http://localhost:$PORT/api" -X PUT \
         -H 'Content-Type: application/json' \
         -d '{"prompts": ["hi"], "tokens_to_generate": 1}'; then
        break
    fi
    sleep 5
done

python tools/text_generation_cli.py "localhost:$PORT"
