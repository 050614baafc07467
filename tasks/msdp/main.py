"""Multi-stage dialogue prompting (MSDP) entry point
(ref: tasks/msdp/main.py).

  python -m tasks.msdp.main --task MSDP-PROMPT --prompt_type knowledge \
      --prompt_file knwl_prompts.jsonl --sample_input_file test.txt \
      --sample_output_file knwl_out.txt --load <ckpt> \
      --tokenizer_type GPT2BPETokenizer --vocab_file vocab.json \
      --merge_file merges.txt
  python -m tasks.msdp.main --task MSDP-EVAL-F1 \
      --guess_file out.txt --answer_file gold.txt
"""
from __future__ import annotations

import argparse

from megatron_tpu.utils.compile_cache import ensure_compile_cache


def get_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser("msdp", description=__doc__)
    p.add_argument("--task", required=True,
                   choices=["MSDP-PROMPT", "MSDP-EVAL-F1"])
    # prompting (ref: tasks/msdp/main.py:22-43)
    p.add_argument("--sample_input_file", default=None)
    p.add_argument("--sample_output_file", default=None)
    p.add_argument("--prompt_file", default=None)
    p.add_argument("--prompt_type", default=None,
                   choices=["knowledge", "response"])
    p.add_argument("--num_prompt_examples", type=int, default=10)
    p.add_argument("--out_seq_length", type=int, default=100)
    p.add_argument("--megatron_api_url", default=None,
                   help="generate via a running REST server instead of "
                        "loading the model in-process")
    p.add_argument("--load", default=None)
    p.add_argument("--tokenizer_type", default="GPT2BPETokenizer")
    p.add_argument("--tokenizer_model", default=None)
    p.add_argument("--vocab_file", default=None)
    p.add_argument("--merge_file", default=None)
    # eval
    p.add_argument("--guess_file", default=None)
    p.add_argument("--answer_file", default=None)
    return p


def main(argv=None) -> int:
    ensure_compile_cache()
    args = get_parser().parse_args(argv)
    if args.task == "MSDP-PROMPT":
        assert args.sample_input_file and args.prompt_file, \
            "MSDP-PROMPT needs --sample_input_file and --prompt_file"
        from tasks.msdp.prompt import run_prompting
        return run_prompting(args)
    assert args.guess_file and args.answer_file, \
        "MSDP-EVAL-F1 needs --guess_file and --answer_file"
    from tasks.msdp.evaluate import evaluate_f1
    evaluate_f1(args.guess_file, args.answer_file)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
