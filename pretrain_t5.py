"""T5 pretraining entry point (ref: /root/reference/pretrain_t5.py).

  python pretrain_t5.py --data_path /data/corpus --vocab_file vocab.txt \
      --tokenizer_type BertWordPieceLowerCase --seq_length 512 \
      --vocab_extra_ids 100 --train_iters 10000 --save ckpts/t5
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
    from megatron_tpu.data.masked_dataset import T5Dataset
    from megatron_tpu.models import t5
    from megatron_tpu.parallel.mesh import build_mesh
    from megatron_tpu.training.pretrain import run_pretrain

    n_devices = len(jax.devices())
    cfg, args = parse_cli(argv, n_devices=n_devices)
    # T5 architecture family (ref: pretrain_t5.py model_provider): encoder-
    # decoder, learned positions, gelu+bias, pre-LN
    cfg = dataclasses.replace(cfg, model=dataclasses.replace(
        cfg.model, use_rotary_emb=False, use_position_embedding=True,
        use_post_ln=False, use_bias=True, norm_type="layernorm",
        activation="gelu", tie_embed_logits=True))

    extra_ids = cfg.data.vocab_extra_ids or 100
    tokenizer = build_tokenizer(
        cfg.data.tokenizer_type or "BertWordPieceLowerCase",
        vocab_file=cfg.data.vocab_file,
        tokenizer_model=cfg.data.tokenizer_model,
        vocab_extra_ids=extra_ids)
    cfg = dataclasses.replace(cfg, model=dataclasses.replace(
        cfg.model, vocab_size=tokenizer.vocab_size)).validate(
        n_devices=n_devices)
    mcfg = cfg.model

    src_paths = cfg.data.data_path or cfg.data.train_data_path
    assert src_paths, "--data_path (or --train_data_path) required"
    prefix = _single_prefix(src_paths, "--data_path")
    sentinel_ids = list(range(tokenizer.vocab_size - extra_ids,
                              tokenizer.vocab_size))

    def make_ds(pfx, n_samples):
        return T5Dataset(
            MMapIndexedDataset(str(pfx)), n_samples, mcfg.seq_length,
            cfg.data.max_seq_length_dec, tokenizer.vocab_size,
            sentinel_ids=sentinel_ids, bos_id=tokenizer.cls,
            eos_id=tokenizer.sep, pad_id=tokenizer.pad,
            seed=cfg.training.seed,
            masked_lm_prob=cfg.data.masked_lm_prob)

    n_samples = cfg.training.train_iters * cfg.training.global_batch_size
    dataset = make_ds(prefix, n_samples)
    valid_dataset = None
    if cfg.data.valid_data_path:  # ref: --valid_data_path eval corpus
        valid_dataset = make_ds(
            _single_prefix(cfg.data.valid_data_path, "--valid_data_path"),
            cfg.training.eval_iters * cfg.training.global_batch_size)

    init_fn = functools.partial(
        t5.t5_init, jax.random.PRNGKey(cfg.training.seed), mcfg)

    def loss_fn(params, mb, mb_rng):
        return t5.t5_loss(params, mb, mcfg, rng=mb_rng,
                          deterministic=mcfg.hidden_dropout == 0.0)

    mesh = build_mesh(cfg.parallel) if n_devices > 1 else None

    pipelined_loss_fn = None
    if mesh is not None and cfg.parallel.pipeline_parallel > 1:
        # pp>1: both stacks pipelined over 'pp' (the reference's split-rank
        # schedule capability, ref: schedules.py:505-535)
        def pipelined_loss_fn(params, batch, rng):
            return t5.t5_pipeline_loss_fn(
                params, batch, cfg.model, mesh,
                vpp=cfg.parallel.virtual_pipeline_chunks, rng=rng,
                deterministic=cfg.model.hidden_dropout == 0.0)

    return run_pretrain(cfg, dataset, init_params_fn=init_fn,
                        loss_fn=loss_fn,
                        axes_fn=lambda m: t5.t5_axes(m), mesh=mesh,
                        valid_dataset=valid_dataset,
                        pipelined_loss_fn=pipelined_loss_fn)


if __name__ == "__main__":
    sys.exit(main())
