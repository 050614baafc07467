"""Main training entry point: pretrain/finetune GPT, Llama, or Falcon.

TPU-native equivalent of the reference's finetune.py (the primary entry,
ref: /root/reference/finetune.py:92-151) and the `pretrain` driver it calls
(ref: megatron/training.py:54-167). One process drives all local devices —
no torchrun; the mesh replaces process groups (SURVEY.md §7).

  python finetune.py --model llama2-7b --data_path 1.0 /data/corpus_document \
      --tokenizer_type SentencePieceTokenizer --tokenizer_model tok.model \
      --tensor_model_parallel_size 8 --train_iters 1000 --save ckpts/run1
"""
from __future__ import annotations

import sys

import jax

from megatron_tpu.utils.compile_cache import ensure_compile_cache
ensure_compile_cache()

from megatron_tpu.utils.tracing import phase  # noqa: E402


@phase("data")
def build_data(cfg, tokenizer, consumed_samples: int, mesh=None):
    """(ref: megatron/training.py:855-939 build_train_valid_test_data_iterators
    + finetune.py:107 dataset provider)"""
    from megatron_tpu.data import BatchIterator, build_train_valid_test_datasets

    tr = cfg.training
    dp = cfg.parallel.data_parallel or 1
    eval_iters = ((tr.train_iters // max(tr.eval_interval, 1)) + 1) * tr.eval_iters
    samples = (tr.train_iters * tr.global_batch_size,
               eval_iters * tr.global_batch_size,
               tr.eval_iters * tr.global_batch_size)
    if cfg.data.train_data_path or cfg.data.valid_data_path \
            or cfg.data.test_data_path:
        # per-split corpora (ref: --train_data_path/--valid_data_path/
        # --test_data_path). The train corpus may also come from
        # --data_path (arguments.py forbids both train sources at once);
        # --split is ignored in this mode — each corpus IS its split.
        def one(paths, n):
            if not paths:
                return None
            ds, _, _ = build_train_valid_test_datasets(
                list(paths), "1,0,0", cfg.model.seq_length, tr.seed,
                n, 0, 0, strict_data=cfg.data.strict_data)
            return ds
        train_ds = one(cfg.data.train_data_path or cfg.data.data_path,
                       samples[0])
        valid_ds = one(cfg.data.valid_data_path, samples[1])
        test_ds = one(cfg.data.test_data_path, samples[2])
    else:
        train_ds, valid_ds, test_ds = build_train_valid_test_datasets(
            cfg.data.data_path, cfg.data.split, cfg.model.seq_length,
            tr.seed, *samples, strict_data=cfg.data.strict_data)

    host_rows = None
    if mesh is not None and jax.process_count() > 1:
        # pod-scale: this host only tokenizes its own dp rows (see
        # multihost.make_global_batch — other rows are never read here).
        # THE mesh from main(): host_rows must match the exact device
        # layout make_global_batch shards against
        from megatron_tpu.parallel.multihost import process_batch_rows
        host_rows = process_batch_rows(mesh, tr.micro_batch_size * dp)

    def make_iter(ds, consumed):
        if ds is None:
            return None
        return BatchIterator(
            ds, tr.micro_batch_size, dp, cfg.num_microbatches,
            consumed_samples=consumed, dataloader_type=cfg.data.dataloader_type,
            seed=tr.seed, eod_token=tokenizer.eod if tokenizer else None,
            reset_position_ids=cfg.data.reset_position_ids,
            reset_attention_mask=cfg.data.reset_attention_mask,
            eod_mask_loss=cfg.data.eod_mask_loss,
            host_rows=host_rows)

    return (make_iter(train_ds, consumed_samples), make_iter(valid_ds, 0),
            make_iter(test_ds, 0))


@phase("init_state")
def init_state(cfg, mesh, rng):
    """The fresh TrainState, born where it will live. With a mesh every
    leaf is created already sharded as the train step wants it: a model
    that needs the mesh does not fit its first device, where an eager
    init would put parameters and both Adam moments whole."""
    from megatron_tpu.training import init_train_state
    if mesh is None:
        return init_train_state(rng, cfg)
    from megatron_tpu.training.train_step import state_shardings
    shapes = jax.eval_shape(lambda: init_train_state(rng, cfg))
    return jax.jit(
        lambda r: init_train_state(r, cfg),
        out_shardings=state_shardings(cfg, mesh, shapes.params))(rng)


def main(argv=None):
    from megatron_tpu.arguments import parse_cli
    from megatron_tpu.config import MegatronConfig
    from megatron_tpu.data import build_tokenizer, restore_data_state
    from megatron_tpu.parallel.mesh import build_mesh
    from megatron_tpu.training import checkpointing as ckpt
    from megatron_tpu.training.loop import train
    from megatron_tpu.utils.logging import print_rank_0

    n_devices = len(jax.devices())
    cfg, args = parse_cli(argv, n_devices=n_devices)

    # --use_checkpoint_args: architecture comes from the checkpoint
    # (ref: megatron/checkpointing.py:476-558)
    if args.use_checkpoint_args and cfg.training.load_dir:
        loaded_cfg = ckpt.load_config_from_checkpoint(cfg.training.load_dir)
        if loaded_cfg is not None:
            import dataclasses
            cfg = dataclasses.replace(cfg, model=loaded_cfg.model)
            cfg = cfg.validate(n_devices=n_devices)

    print_rank_0(f"devices: {n_devices} | mesh: tp={cfg.parallel.tensor_parallel} "
                 f"pp={cfg.parallel.pipeline_parallel} "
                 f"dp={cfg.parallel.data_parallel} "
                 f"sp={cfg.parallel.sequence_parallel}")
    mesh = build_mesh(cfg.parallel) if n_devices > 1 else None

    tokenizer = None
    if cfg.data.tokenizer_model or cfg.data.vocab_file:
        tokenizer = build_tokenizer(
            cfg.data.tokenizer_type, vocab_file=cfg.data.vocab_file,
            merge_file=cfg.data.merge_file,
            tokenizer_model=cfg.data.tokenizer_model,
            vocab_extra_ids=cfg.data.vocab_extra_ids,
            vocab_extra_ids_list=cfg.data.vocab_extra_ids_list,
            new_tokens=cfg.data.new_tokens)
        import dataclasses
        cfg = dataclasses.replace(cfg, model=dataclasses.replace(
            cfg.model, vocab_size=tokenizer.vocab_size))

    rng = jax.random.PRNGKey(cfg.training.seed)
    state = init_state(cfg, mesh, rng)
    start_iteration, consumed = 0, 0
    data_state, quarantine = None, []
    load_dir = cfg.training.load_dir or cfg.training.checkpoint_dir
    if load_dir:
        loaded = ckpt.load_checkpoint(
            load_dir, state, finetune=cfg.training.finetune,
            no_load_optim=cfg.training.no_load_optim,
            resilience=cfg.resilience)
        _, start_iteration, consumed = loaded
        data_state, quarantine = loaded.data_state, loaded.quarantine
        if loaded.state is not None:
            state = loaded.state

    train_it, valid_it, _ = build_data(cfg, tokenizer, consumed, mesh=mesh)
    assert train_it is not None, "--data_path produced no training data"
    restore_data_state(train_it, data_state)

    if getattr(args, "lora_rank", 0):
        # LoRA finetune: train ONLY the low-rank adapter factors with
        # the (possibly checkpoint-loaded) base frozen, then export the
        # versioned .npz the serving bank loads (--adapter_slots /
        # ServingEngine.register_adapter) — the training side feeding
        # the serving side end to end (training/lora.py).
        from megatron_tpu.training.lora import run_lora_finetune
        export = args.lora_export or (
            f"{cfg.training.checkpoint_dir}/adapter.npz"
            if cfg.training.checkpoint_dir else "adapter.npz")
        _, last_loss = run_lora_finetune(
            cfg, state.params, train_it, rank=args.lora_rank,
            alpha=args.lora_alpha, iters=cfg.training.train_iters,
            lr=cfg.optimizer.lr, seed=cfg.training.seed,
            export_path=export,
            log_interval=cfg.training.log_interval)
        print_rank_0(f"lora finetune done: final loss {last_loss:.4f}, "
                     f"adapter at {export}")
        return 0

    save_fn = None
    if cfg.training.checkpoint_dir:
        def save_fn(st, iteration, consumed_samples, data_state=None,
                    quarantine=None):
            # data_state/quarantine: the loop's exact-resume snapshot of
            # the training iterator, persisted in checkpoint metadata so
            # a restart replays the identical batch sequence
            ckpt.save_checkpoint(cfg.training.checkpoint_dir, st, cfg,
                                 iteration, consumed_samples,
                                 data_state=data_state,
                                 quarantine=quarantine)

    # divergence-rollback hooks (docs/resilience.md): restore the newest
    # valid checkpoint and rebuild the data stream at its EXACT saved
    # position — the loop replays the identical order and quarantines
    # the poisoned step window (never a re-seeded order). Rollback only
    # targets checkpoints THIS run writes (--save): restoring the --load
    # base would resurrect its iteration counter / optimizer state (a
    # finetune base "resumes" at its pretraining iteration and the loop
    # would just exit)
    load_fn = None
    if cfg.training.checkpoint_dir:
        def load_fn():
            return ckpt.load_checkpoint(cfg.training.checkpoint_dir,
                                        state,
                                        resilience=cfg.resilience)

    def reset_data_fn(consumed_samples, rollbacks, data_state=None):
        it, _, _ = build_data(cfg, tokenizer, consumed_samples,
                              mesh=mesh)
        restore_data_state(it, data_state)
        return it

    state, consumed = train(
        cfg, train_it, valid_it, mesh=mesh, state=state, rng=rng,
        start_iteration=start_iteration, consumed_samples=consumed,
        save_fn=save_fn, load_fn=load_fn, reset_data_fn=reset_data_fn,
        quarantine_log=quarantine)
    print_rank_0(f"training done at consumed_samples={consumed}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
