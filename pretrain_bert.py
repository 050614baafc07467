"""BERT pretraining entry point (ref: /root/reference/pretrain_bert.py).

  python pretrain_bert.py --data_path /data/corpus --vocab_file vocab.txt \
      --tokenizer_type BertWordPieceLowerCase --seq_length 128 \
      --num_layers 12 --hidden_size 768 --num_attention_heads 12 \
      --train_iters 10000 --save ckpts/bert

The corpus is a standard indexed dataset; MLM+NSP samples come from
BertDataset (doc-halves pairing) — for mapping-backed sentence-pair
sampling over a sentence-split corpus use
megatron_tpu.data.ict_dataset.BertSentencePairDataset.
"""
from __future__ import annotations

import dataclasses
import functools
import sys

import jax

from megatron_tpu.utils.compile_cache import ensure_compile_cache
ensure_compile_cache()



def _single_prefix(paths, flag):
    """BERT/T5/ICT pretraining consumes exactly ONE corpus prefix — the
    weighted blend syntax is a GPT-dataset feature (finetune.py); fail
    loudly instead of silently training on paths[-1]."""
    paths = list(paths)
    if len(paths) != 1:
        raise SystemExit(
            f"{flag} takes exactly one indexed-dataset prefix here "
            f"(got {paths}); weighted blending is only supported by the "
            "GPT data pipeline (finetune.py)")
    return paths[0]


def main(argv=None):
    from megatron_tpu.arguments import parse_cli
    from megatron_tpu.data import build_tokenizer
    from megatron_tpu.data.indexed_dataset import MMapIndexedDataset
    from megatron_tpu.data.masked_dataset import BertDataset
    from megatron_tpu.models import bert
    from megatron_tpu.parallel.mesh import build_mesh
    from megatron_tpu.training.pretrain import run_pretrain

    n_devices = len(jax.devices())
    cfg, args = parse_cli(argv, n_devices=n_devices)
    # force the BERT architecture family (ref: pretrain_bert.py
    # model_provider -> BertModel): post-LN, learned positions, gelu+bias
    cfg = dataclasses.replace(cfg, model=dataclasses.replace(
        cfg.model, use_rotary_emb=False, use_position_embedding=True,
        use_post_ln=True, use_bias=True, norm_type="layernorm",
        activation="gelu", tie_embed_logits=True))

    tokenizer = build_tokenizer(
        cfg.data.tokenizer_type or "BertWordPieceLowerCase",
        vocab_file=cfg.data.vocab_file,
        tokenizer_model=cfg.data.tokenizer_model)
    cfg = dataclasses.replace(cfg, model=dataclasses.replace(
        cfg.model, vocab_size=tokenizer.vocab_size)).validate(
        n_devices=n_devices)
    mcfg = cfg.model

    src_paths = cfg.data.data_path or cfg.data.train_data_path
    assert src_paths, "--data_path (or --train_data_path) required"
    prefix = _single_prefix(src_paths, "--data_path")

    def make_ds(pfx, n_samples):
        return BertDataset(
            MMapIndexedDataset(str(pfx)), n_samples, mcfg.seq_length,
            tokenizer.vocab_size, cls_id=tokenizer.cls,
            sep_id=tokenizer.sep, mask_id=tokenizer.mask,
            pad_id=tokenizer.pad, seed=cfg.training.seed,
            masked_lm_prob=cfg.data.masked_lm_prob)

    n_samples = cfg.training.train_iters * cfg.training.global_batch_size
    dataset = make_ds(prefix, n_samples)
    valid_dataset = None
    if cfg.data.valid_data_path:  # ref: --valid_data_path eval corpus
        valid_dataset = make_ds(
            _single_prefix(cfg.data.valid_data_path, "--valid_data_path"),
            cfg.training.eval_iters * cfg.training.global_batch_size)

    init_fn = functools.partial(
        bert.bert_init, jax.random.PRNGKey(cfg.training.seed), mcfg)

    def loss_fn(params, mb, mb_rng):
        return bert.bert_loss(params, mb, mcfg, rng=mb_rng,
                              deterministic=mcfg.hidden_dropout == 0.0)

    mesh = build_mesh(cfg.parallel) if n_devices > 1 else None
    return run_pretrain(cfg, dataset, init_params_fn=init_fn,
                        loss_fn=loss_fn,
                        axes_fn=lambda m: bert.bert_axes(m), mesh=mesh,
                        valid_dataset=valid_dataset,
                        # pp>1: MLM/NSP pipelined through the generic 1F1B
                        # core (ref: schedules.py:606-722 + pretrain_bert
                        # forward_step)
                        pipelined_spec=bert.bert_1f1b_fns)


if __name__ == "__main__":
    sys.exit(main())
