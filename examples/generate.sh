#!/bin/bash
# Serve a trained checkpoint over the REST generation API
# (ref: the run_text_generation_server entry; here inference/server.py,
# same /api request schema + static UI).
#
# Usage: CHECKPOINT_PATH=./checkpoints/llama2-7b TOKENIZER_MODEL=tok.model \
#        bash examples/generate.sh
# (the tool's default tokenizer is SentencePieceTokenizer, which needs
# --tokenizer_model; pass other --tokenizer_type flags after the script)
set -euo pipefail

CHECKPOINT_PATH=${CHECKPOINT_PATH:?set CHECKPOINT_PATH}
PORT=${PORT:-5000}

python tools/run_text_generation_server.py \
  --load "$CHECKPOINT_PATH" \
  --model "${MODEL:-llama}" \
  --port "$PORT" \
  ${TOKENIZER_MODEL:+--tokenizer_model "$TOKENIZER_MODEL"} \
  "$@"
