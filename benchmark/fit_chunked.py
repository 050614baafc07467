"""Does a serving cell that prefills by chunks fit? `fit.py::fit_serve`
compiles the decode program and ONE prefill program of the mix's longest
prompt; an engine with `prefill_chunk` never runs that program. This compiles,
for a described `v5e:2x2` in the sandbox (no chip, nothing runs), what such an
engine does run, at the cell's real size, and prints the compiler's memory
count of each (`fit.report`):

    JAX_PLATFORMS=cpu python benchmark/fit_chunked.py \
        --workload command-a-plus.serve-longdoc-32k [--slots N] [--one_kind]

the decode program; the one-shot prefill of a prompt as long as the chunk
(`_prefill`, 1 x chunk); the chunk program at its largest (`_chunk_fwd`, a
full chunk continuing a sequence's own cache); the landing of a finished
prefill in its slot (`_insert`). `--one_kind` holds every layer's cache as a
whole region (no rings: `window_layer_period` 0 and no window), to show what
the pool would cost as one kind. A rehearsal tool like `fit.py`: it reaches
into the engine's attributes.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--slots", type=int, default=None)
    p.add_argument("--one_kind", action="store_true")
    args = p.parse_args()
    sys.path.insert(0, ROOT)
    from benchmark import fit, run as bench_run
    _, _, config, mix = bench_run.load_cell(args.workload)

    import jax
    import jax.numpy as jnp
    import numpy as np
    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from megatron_tpu.arguments import parse_cli
    from megatron_tpu.config import ServingConfig
    from megatron_tpu.inference.generation import Generator
    from megatron_tpu.models import language_model as lm
    from megatron_tpu.serving import ServingEngine
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    real_backend = jax.default_backend
    jax.default_backend = lambda: "cpu"       # build the engine as a CPU one
    cfg, _ = parse_cli([*config["cli"], "--bf16"], n_devices=1)
    mcfg = cfg.model
    serving = dict(mix["serving"])
    if args.slots:
        serving["num_slots"] = args.slots
    serving = ServingConfig(**serving).validate(mcfg)
    if args.one_kind:
        mcfg = dataclasses.replace(mcfg, window_layer_period=0,
                                   sliding_window=None)
    shapes = jax.eval_shape(lambda: lm.model_init(jax.random.PRNGKey(0), mcfg))
    params = jax.tree.map(lambda s: np.zeros(s.shape, s.dtype), shapes)
    eng = ServingEngine(Generator(params, mcfg, eos_id=-1, pad_id=0),
                        serving, start=False)
    one = SingleDeviceSharding(topo.devices[0])
    spec = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            np.shape(x), x.dtype if hasattr(x, "dtype")
            else jnp.asarray(x).dtype, sharding=one), tree)
    nbytes = lambda tree: sum(x.size * x.dtype.itemsize
                              for x in jax.tree.leaves(tree))
    print(json.dumps({
        "slots": serving.num_slots, "one_kind": args.one_kind,
        "parameters_m": sum(x.size for x in jax.tree.leaves(shapes)) / 1e6,
        "weights_gib": nbytes(shapes) / fit.GIB,
        "kv_pool_gib": nbytes(eng.pool.caches) / fit.GIB,
        "kv_bytes_per_slot": eng.pool.bytes_per_slot()}), flush=True)
    jax.default_backend = lambda: "tpu"
    state = (eng._p_dec, eng.pool.caches, eng._last_logits, eng._rngs)
    chunk = serving.prefill_chunk
    sub = eng.pool.make_prefill_caches(1)
    last = np.zeros((mcfg.padded_vocab_size,), np.float32)
    programs = [
        ("decode", eng._decode, (
            *state, eng._d_lengths, eng._d_temps, eng._d_top_ks,
            eng._d_top_ps, eng._d_reject, eng._d_masks)),
        (f"prefill[1x{chunk}]", eng._prefill, (
            *state, np.zeros((1, chunk), np.int32), np.zeros((1,), np.int32),
            np.zeros((1,), np.int32), np.zeros((1, 2), np.uint32))),
        (f"chunk[1x{chunk}]", eng._chunk_fwd, (
            eng._p_pre, sub, np.zeros((1, chunk), np.int32), np.int32(0),
            np.int32(0))),
    ]
    for name, fn, fn_args in programs:
        try:
            fit.report(name, fn.lower(*spec(fn_args), None, None).compile())
        except Exception as e:      # the compiler's refusal is the answer
            print(json.dumps({"program": name,
                              "refused": str(e).splitlines()[0][:400]}),
                  flush=True)
    try:
        fit.report("insert", eng._insert.lower(*spec((
            *state, sub, np.int32(0), np.int32(0), last,
            np.zeros((2,), np.uint32)))).compile())
    except Exception as e:
        print(json.dumps({"program": "insert",
                          "refused": str(e).splitlines()[0][:400]}),
              flush=True)
    jax.default_backend = real_backend
    eng.close()


if __name__ == "__main__":
    main()
