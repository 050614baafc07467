"""ICT (inverse cloze task) biencoder pretraining entry point
(ref: /root/reference/pretrain_ict.py).

  python pretrain_ict.py --data_path /data/sentences \
      --titles_data_path /data/titles --vocab_file vocab.txt \
      --tokenizer_type BertWordPieceLowerCase --seq_length 256 \
      --train_iters 10000 --save ckpts/ict

`--data_path` must point to a SENTENCE-split indexed dataset (one sentence
per row, documents delimited by the dataset's doc_idx);
`--titles_data_path` holds one title row per document.
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
    from megatron_tpu.data.ict_dataset import ICTDataset
    from megatron_tpu.data.indexed_dataset import MMapIndexedDataset
    from megatron_tpu.models import biencoder
    from megatron_tpu.parallel.mesh import build_mesh
    from megatron_tpu.training.pretrain import run_pretrain

    def extra_args(p):
        p.add_argument("--titles_data_path", type=str, default=None)
        p.add_argument("--valid_titles_data_path", type=str, default=None,
                       help="titles for the --valid_data_path corpus "
                            "(required with it when --titles_data_path "
                            "is used: titles index per-corpus doc ids)")
        p.add_argument("--ict_head_size", type=int, default=128)
        p.add_argument("--query_in_block_prob", type=float, default=0.1)
        p.add_argument("--biencoder_shared_query_context_model",
                       action="store_true")
        return p  # extra_args_provider contract (ref: finetune.py:129-138)

    n_devices = len(jax.devices())
    cfg, args = parse_cli(argv, n_devices=n_devices,
                          extra_args_provider=extra_args)
    # BERT-family towers (ref: pretrain_ict.py model_provider ->
    # biencoder_model_provider)
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

    def make_ds(pfx, titles_path):
        sentences = MMapIndexedDataset(str(pfx))
        titles = (MMapIndexedDataset(titles_path) if titles_path else None)
        return ICTDataset(
            sentences, sentences.doc_idx, titles,
            max_seq_length=mcfg.seq_length,
            query_in_block_prob=args.query_in_block_prob,
            cls_id=tokenizer.cls, sep_id=tokenizer.sep,
            pad_id=tokenizer.pad, seed=cfg.training.seed,
            sizes=sentences.sizes)

    dataset = make_ds(prefix, args.titles_data_path)
    valid_dataset = None
    if cfg.data.valid_data_path:  # ref: --valid_data_path eval corpus
        if args.titles_data_path and not args.valid_titles_data_path:
            # titles are indexed by doc id WITHIN a corpus — reusing the
            # train titles against the valid corpus would silently pair
            # wrong titles (or crash on a doc-count mismatch)
            raise SystemExit("--valid_data_path with --titles_data_path "
                             "requires --valid_titles_data_path")
        valid_dataset = make_ds(
            _single_prefix(cfg.data.valid_data_path, "--valid_data_path"),
            args.valid_titles_data_path)

    shared = args.biencoder_shared_query_context_model
    init_fn = functools.partial(
        biencoder.biencoder_init, jax.random.PRNGKey(cfg.training.seed),
        mcfg, ict_head_size=args.ict_head_size, shared=shared)

    def loss_fn(params, mb, mb_rng):
        loss, _ = biencoder.retrieval_loss(
            params, mb, mcfg, rng=mb_rng,
            deterministic=mcfg.hidden_dropout == 0.0)
        return loss

    mesh = build_mesh(cfg.parallel) if n_devices > 1 else None
    return run_pretrain(
        cfg, dataset, init_params_fn=init_fn, loss_fn=loss_fn,
        axes_fn=lambda m: biencoder.biencoder_axes(
            m, ict_head_size=args.ict_head_size, shared=shared), mesh=mesh,
        valid_dataset=valid_dataset)


if __name__ == "__main__":
    sys.exit(main())
