"""Task-evaluation entry point (ref: tasks/main.py).

Usage:
  python -m tasks.main --task WIKITEXT103 --valid_data wiki.test.tokens \
      --load <checkpoint_root> --tokenizer_type HFTokenizer \
      --tokenizer_model <name-or-path> [--overlapping_eval 32]
  python -m tasks.main --task LAMBADA --valid_data lambada.jsonl \
      --load <checkpoint_root> [--strict_lambada]

The model config comes from the checkpoint (`use_checkpoint_args`
semantics, ref: checkpointing.py:476-558); metrics print in the
reference's schema (ref: tasks/zeroshot_gpt/evaluate.py:146-174).
"""
from __future__ import annotations

import argparse
import json

from megatron_tpu.utils.compile_cache import ensure_compile_cache


def get_tasks_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser("tasks", description=__doc__)
    p.add_argument("--task", required=True,
                   choices=["WIKITEXT103", "LAMBADA", "MNLI", "QQP", "RACE",
                            "NQ", "RET-FINETUNE-NQ"],
                   help="Task name (ref: tasks/main.py:19; NQ = ORQA "
                        "retriever eval, ref: tasks/orqa/evaluate_orqa.py; "
                        "RET-FINETUNE-NQ = supervised retriever finetune, "
                        "ref: tasks/orqa/supervised/finetune.py).")
    p.add_argument("--valid_data", nargs="+", required=True)
    p.add_argument("--train_data", nargs="*", default=None,
                   help="finetuning data (MNLI/QQP/RACE)")
    p.add_argument("--load", default=None,
                   help="checkpoint root (tracker + iter dirs); required "
                        "for zero-shot tasks")
    p.add_argument("--pretrained_checkpoint", default=None,
                   help="BERT pretraining checkpoint for finetune tasks")
    p.add_argument("--tokenizer_type", default="HFTokenizer")
    p.add_argument("--tokenizer_model", default=None)
    p.add_argument("--vocab_file", default=None)
    p.add_argument("--merge_file", default=None)
    p.add_argument("--overlapping_eval", type=int, default=32,
                   help="sliding-window stride (ref: tasks/main.py:33-34)")
    p.add_argument("--strict_lambada", action="store_true")
    p.add_argument("--micro_batch_size", type=int, default=8)
    p.add_argument("--seq_length", type=int, default=None,
                   help="override eval window (default: model seq_length)")
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--lr", type=float, default=5e-5)
    # model shape for finetune tasks without a checkpoint config
    p.add_argument("--num_layers", type=int, default=12)
    p.add_argument("--hidden_size", type=int, default=768)
    p.add_argument("--num_attention_heads", type=int, default=12)
    # retriever eval (ref: tasks/main.py:38-51 retriever args)
    p.add_argument("--evidence_data_path", default=None,
                   help="DPR-style evidence TSV (id, text, title)")
    p.add_argument("--embedding_path", default=None,
                   help="evidence embedding store (.npz) built by "
                        "tools/create_doc_index.py")
    p.add_argument("--retriever_seq_length", type=int, default=256)
    p.add_argument("--faiss_topk_retrievals", type=int, default=100)
    p.add_argument("--faiss_match", default="string",
                   choices=["string", "regex"])
    p.add_argument("--ict_head_size", type=int, default=128)
    p.add_argument("--biencoder_shared_query_context_model",
                   action="store_true")
    # supervised retriever finetuning (ref: tasks/main.py:53-71)
    p.add_argument("--train_with_neg", action="store_true")
    p.add_argument("--train_hard_neg", type=int, default=0)
    p.add_argument("--val_av_rank_hard_neg", type=int, default=30)
    p.add_argument("--val_av_rank_other_neg", type=int, default=30)
    p.add_argument("--retriever_score_scaling", action="store_true")
    p.add_argument("--sample_rate", type=float, default=1.0,
                   help="subsample fraction of the supervised train set")
    return p


def build_cls_sep_tokenizer(args):
    """A [CLS]/[SEP]/[PAD]-style tokenizer or a clear error — BERT-family
    tasks (GLUE/RACE/retrieval) cannot run on a GPT-style tokenizer."""
    from megatron_tpu.data.tokenizers import build_tokenizer
    tok_type = args.tokenizer_type
    if tok_type == "HFTokenizer" and args.vocab_file:
        # a bare --vocab_file implies WordPiece
        tok_type = "BertWordPieceLowerCase"
    tokenizer = build_tokenizer(
        tok_type, vocab_file=args.vocab_file, merge_file=args.merge_file,
        tokenizer_model=args.tokenizer_model)
    for attr in ("cls", "sep", "pad"):
        if getattr(tokenizer, attr, None) is None:
            raise SystemExit(
                f"--task {args.task} needs a tokenizer with [CLS]/[SEP]/"
                f"[PAD] ids (e.g. --tokenizer_type BertWordPieceLowerCase "
                f"--vocab_file vocab.txt); {tok_type} has no {attr!r}")
    return tokenizer


def run_ret_finetune_task(args) -> dict:
    """Supervised retriever finetune on DPR-format NQ
    (ref: tasks/orqa/supervised/finetune.py)."""
    from megatron_tpu.config import (MegatronConfig, OptimizerConfig,
                                     TrainingConfig)
    from megatron_tpu.models.bert import bert_config
    from tasks.orqa.data import NQSupervisedDataset
    from tasks.orqa.finetune import finetune_retriever

    tokenizer = build_cls_sep_tokenizer(args)
    seq = args.retriever_seq_length
    model = bert_config(
        num_layers=args.num_layers, hidden_size=args.hidden_size,
        num_attention_heads=args.num_attention_heads,
        vocab_size=tokenizer.vocab_size, seq_length=seq,
        max_position_embeddings=seq)
    cfg = MegatronConfig(
        model=model,
        optimizer=OptimizerConfig(lr=args.lr, clip_grad=1.0),
        training=TrainingConfig(micro_batch_size=args.micro_batch_size,
                                global_batch_size=args.micro_batch_size,
                                train_iters=1),
    ).validate(n_devices=1)

    train_ds = NQSupervisedDataset(
        args.train_data or [], tokenizer, seq,
        train_with_neg=args.train_with_neg,
        train_hard_neg=args.train_hard_neg,
        sample_rate=args.sample_rate)
    valid_ds = NQSupervisedDataset(
        args.valid_data, tokenizer, seq, evaluate=True,
        val_av_rank_hard_neg=args.val_av_rank_hard_neg,
        val_av_rank_other_neg=args.val_av_rank_other_neg)
    result = finetune_retriever(
        cfg, train_ds, valid_ds, epochs=args.epochs,
        score_scaling=args.retriever_score_scaling,
        pretrained_checkpoint=args.pretrained_checkpoint,
        ict_head_size=args.ict_head_size,
        shared=args.biencoder_shared_query_context_model)
    print(json.dumps({"task": "RET-FINETUNE-NQ", **result["final"]}))
    return result["final"]


def load_biencoder(args, vocab_size: int, seq_length: int):
    """Biencoder checkpoint -> (params, ModelConfig)
    (ref: checkpointing.py load_biencoder_checkpoint)."""
    import jax

    from megatron_tpu.models import biencoder
    from megatron_tpu.models.bert import bert_config
    from megatron_tpu.training.checkpointing import (
        load_checkpoint, load_config_from_checkpoint)
    from megatron_tpu.training.train_step import TrainState

    cfg = load_config_from_checkpoint(args.load)
    mcfg = cfg.model if cfg is not None else bert_config(
        num_layers=args.num_layers, hidden_size=args.hidden_size,
        num_attention_heads=args.num_attention_heads,
        vocab_size=vocab_size, seq_length=seq_length,
        max_position_embeddings=seq_length)
    params = biencoder.biencoder_init(
        jax.random.PRNGKey(0), mcfg, ict_head_size=args.ict_head_size,
        shared=args.biencoder_shared_query_context_model)
    example = TrainState(params=params, opt_state=None, iteration=0)
    state, _, _ = load_checkpoint(args.load, example, no_load_optim=True)
    if state is None:
        raise SystemExit(f"no biencoder checkpoint under {args.load}")
    return state.params, mcfg


def run_nq_task(args) -> dict:
    """ORQA retriever eval: NQ top-k retrieval accuracy
    (ref: tasks/orqa/evaluate_orqa.py + evaluate_utils.py)."""
    from megatron_tpu.data.orqa_dataset import OpenRetrievalEvidenceDataset
    from megatron_tpu.data.tokenizers import build_tokenizer
    from tasks.orqa.evaluate import ORQAEvaluator

    assert args.load, "--task NQ needs --load (biencoder checkpoint)"
    assert args.evidence_data_path and args.embedding_path, \
        "--task NQ needs --evidence_data_path and --embedding_path"
    tokenizer = build_tokenizer(
        args.tokenizer_type, vocab_file=args.vocab_file,
        merge_file=args.merge_file, tokenizer_model=args.tokenizer_model)
    params, mcfg = load_biencoder(args, tokenizer.vocab_size,
                                  args.retriever_seq_length)
    evidence = OpenRetrievalEvidenceDataset(
        args.evidence_data_path, tokenizer, args.retriever_seq_length)
    evaluator = ORQAEvaluator(params, mcfg, evidence_dataset=evidence,
                              embedding_path=args.embedding_path)
    metrics = {}
    for path in args.valid_data:
        metrics[path] = evaluator.evaluate(
            path, tokenizer, seq_length=args.retriever_seq_length,
            top_k=args.faiss_topk_retrievals,
            batch_size=args.micro_batch_size,
            match_type=args.faiss_match)
    print(json.dumps({"task": "NQ", **metrics}))
    return metrics


def run_finetune_task(args) -> dict:
    """GLUE (MNLI/QQP) classification and RACE multiple-choice finetuning
    (ref: tasks/glue/finetune.py, tasks/race/finetune.py)."""
    from megatron_tpu.config import (MegatronConfig, OptimizerConfig,
                                     TrainingConfig)
    from megatron_tpu.models.bert import bert_config
    from tasks.finetune_utils import finetune_and_evaluate

    tokenizer = build_cls_sep_tokenizer(args)
    seq = args.seq_length or 512
    model = bert_config(
        num_layers=args.num_layers, hidden_size=args.hidden_size,
        num_attention_heads=args.num_attention_heads,
        vocab_size=tokenizer.vocab_size, seq_length=seq,
        max_position_embeddings=seq)
    cfg = MegatronConfig(
        model=model,
        optimizer=OptimizerConfig(lr=args.lr, clip_grad=1.0),
        training=TrainingConfig(micro_batch_size=args.micro_batch_size,
                                global_batch_size=args.micro_batch_size,
                                train_iters=1),
    ).validate(n_devices=1)

    if args.task in ("MNLI", "QQP"):
        from tasks.glue.data import GlueDataset, read_mnli, read_qqp
        read = read_mnli if args.task == "MNLI" else read_qqp
        train_rows = [r for p in (args.train_data or []) for r in read(p)]
        valid_rows = [r for p in args.valid_data for r in read(p)]
        train_ds = GlueDataset(train_rows, tokenizer, seq)
        valid_ds = GlueDataset(valid_rows, tokenizer, seq)
        kind = "classification"
        num_classes = 3 if args.task == "MNLI" else 2
    else:  # RACE
        from tasks.race.data import RaceDataset, read_race
        train_rows = [r for p in (args.train_data or [])
                      for r in read_race(p)]
        valid_rows = [r for p in args.valid_data for r in read_race(p)]
        train_ds = RaceDataset(train_rows, tokenizer, seq)
        valid_ds = RaceDataset(valid_rows, tokenizer, seq)
        kind = "multichoice"
        num_classes = 4

    result = finetune_and_evaluate(
        cfg, train_ds, valid_ds, kind=kind, num_classes=num_classes,
        epochs=args.epochs,
        pretrained_checkpoint=args.pretrained_checkpoint)
    metrics = {"best accuracy": result["best_accuracy"],
               "last accuracy": result["last_accuracy"]}
    print(json.dumps({"task": args.task, **metrics}))
    return metrics


def run_task(args) -> dict:
    import jax

    from megatron_tpu.data.tokenizers import build_tokenizer
    from megatron_tpu.training import init_train_state
    from megatron_tpu.training.checkpointing import (
        load_checkpoint, load_config_from_checkpoint)
    from megatron_tpu.training.train_step import TrainState
    from tasks.zeroshot_gpt import evaluate as ev
    from tasks.zeroshot_gpt.datasets import (build_lambada_dataset,
                                             build_wikitext_dataset)

    cfg = load_config_from_checkpoint(args.load)
    if cfg is None:
        raise SystemExit(f"no checkpoint found under {args.load}")
    tokenizer = build_tokenizer(
        args.tokenizer_type, vocab_file=args.vocab_file,
        merge_file=args.merge_file, tokenizer_model=args.tokenizer_model)

    example = init_train_state(jax.random.PRNGKey(0), cfg)
    state, _, _ = load_checkpoint(args.load, example, no_load_optim=True)
    state = TrainState(params=state.params, opt_state=None,
                       iteration=state.iteration)

    seq_len = args.seq_length or cfg.model.seq_length
    path = args.valid_data[0]
    if args.task == "WIKITEXT103":
        ds = build_wikitext_dataset(path, tokenizer, seq_len,
                                    overlapping_eval=args.overlapping_eval)
        stats = ev.evaluate_dataset(state.params, ds, cfg,
                                    batch_size=args.micro_batch_size,
                                    log_every=10)
        metrics = ev.wikitext_metrics(stats, ds)
    else:
        ds = build_lambada_dataset(path, tokenizer, seq_len,
                                   strict=args.strict_lambada)
        stats = ev.evaluate_dataset(state.params, ds, cfg,
                                    batch_size=args.micro_batch_size,
                                    log_every=10)
        metrics = ev.lambada_metrics(stats)

    line = f" validation results on {args.task} | " + " | ".join(
        f"{k}: {v:.4E}" if isinstance(v, float) else f"{k}: {v}"
        for k, v in metrics.items())
    print("-" * (len(line) + 1))
    print(line)
    print("-" * (len(line) + 1))
    print(json.dumps({"task": args.task, **metrics}))
    return metrics


def main():
    ensure_compile_cache()
    args = get_tasks_parser().parse_args()
    if args.task in ("MNLI", "QQP", "RACE"):
        run_finetune_task(args)
    elif args.task == "NQ":
        run_nq_task(args)
    elif args.task == "RET-FINETUNE-NQ":
        run_ret_finetune_task(args)
    else:
        assert args.load, "--load required for zero-shot tasks"
        run_task(args)


if __name__ == "__main__":
    main()
