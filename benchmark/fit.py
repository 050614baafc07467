"""Does a cell's depth fit? Compiles the cell's main program at its real
size for a described `v5e:2x2` in the sandbox (no chip, nothing runs) and
prints the compiler's memory count per device.

    JAX_PLATFORMS=cpu python benchmark/fit.py --workload falcon-7b.train-2k [--layers N]

The third rehearsal of the `on-chip-measurement` guide. The program asks
`jax.default_backend()` to choose its kernels, so this script answers "tpu"
for it while it traces: steering that belongs here and not in the program.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
GIB = 2.0 ** 30


def report(name, compiled):
    m = compiled.memory_analysis()
    text = compiled.as_text()
    rec = {"program": name,
           "arguments_gib": m.argument_size_in_bytes / GIB,
           "outputs_gib": m.output_size_in_bytes / GIB,
           "aliased_gib": m.alias_size_in_bytes / GIB,
           "temporaries_gib": m.temp_size_in_bytes / GIB,
           "total_gib": (m.argument_size_in_bytes + m.output_size_in_bytes
                         - m.alias_size_in_bytes + m.temp_size_in_bytes) / GIB,
           "mosaic_calls": text.count("tpu_custom_call")}
    print(json.dumps(rec), flush=True)
    return rec


def with_layers(cli, layers):
    cli = list(cli)
    if layers is not None:
        cli[cli.index("--num_layers") + 1] = str(layers)
    return cli


def fit_train(config, job, chips, layers, topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding
    from megatron_tpu.arguments import parse_cli
    from megatron_tpu.parallel import mesh as mesh_mod
    from megatron_tpu.training import init_train_state, make_train_step
    from megatron_tpu.training.train_step import state_shardings

    argv = [*with_layers(config["cli"], layers), *job["cli"],
            "--data_path", "none", "--split", "100,0,0", "--train_iters", "8"]
    cfg, _ = parse_cli(argv, n_devices=chips)
    shapes = jax.eval_shape(
        lambda: init_train_state(jax.random.PRNGKey(0), cfg))
    n_micro, seq = cfg.num_microbatches, cfg.model.seq_length
    mbs = cfg.training.micro_batch_size
    if chips == 1:
        mesh, one = None, SingleDeviceSharding(topo.devices[0])
        put = lambda s, _sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one)
        state = jax.tree.map(lambda s: put(s, None), shapes)
        batch_sh = rng_sh = one
    else:
        mesh = mesh_mod.build_mesh(cfg.parallel, devices=topo.devices[:chips])
        sh = state_shardings(cfg, mesh, shapes.params)
        state = jax.tree.map(
            lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h),
            shapes, sh)
        batch_sh = NamedSharding(mesh, P(None, "dp"))
        rng_sh = NamedSharding(mesh, P())
    batch = {"tokens": jax.ShapeDtypeStruct((n_micro, mbs, seq + 1),
                                            jnp.int32, sharding=batch_sh),
             "loss_mask": jax.ShapeDtypeStruct((n_micro, mbs, seq),
                                               jnp.float32, sharding=batch_sh)}
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rng_sh)
    step = make_train_step(cfg, mesh=mesh)
    lower = getattr(step, "_fn", step).lower
    if mesh is not None:
        with jax.set_mesh(mesh):
            compiled = lower(state, batch, rng).compile()
    else:
        compiled = lower(state, batch, rng).compile()
    n_params = sum(x.size for x in jax.tree.leaves(shapes.params))
    print(json.dumps({"layers": cfg.model.num_layers, "chips": chips,
                      "parameters_m": n_params / 1e6}), flush=True)
    return report("train_step", compiled)


def fit_serve(config, mix, layers, topo):
    """The decode program and the largest prefill program of the engine as
    the driver builds it, compiled from the engine's own argument shapes.
    The engine is built here on the CPU over zero weights, so this reaches
    into its attributes (`_decode`, `_prefill`, `_p_dec`, ...): a rehearsal
    tool, not part of the measuring path."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding
    from megatron_tpu.arguments import parse_cli
    from megatron_tpu.config import ServingConfig
    from megatron_tpu.inference.generation import Generator
    from megatron_tpu.models import language_model as lm
    from megatron_tpu.serving import ServingEngine

    real_backend = jax.default_backend
    jax.default_backend = lambda: "cpu"       # build the engine as a CPU one
    cfg, _ = parse_cli([*with_layers(config["cli"], layers), "--bf16"],
                       n_devices=1)
    mcfg = cfg.model
    shapes = jax.eval_shape(lambda: lm.model_init(jax.random.PRNGKey(0), mcfg))
    params = jax.tree.map(lambda s: np.zeros(s.shape, s.dtype), shapes)
    serving = ServingConfig(**mix["serving"]).validate(mcfg)
    eng = ServingEngine(Generator(params, mcfg, eos_id=-1, pad_id=0),
                        serving, start=False)
    one = SingleDeviceSharding(topo.devices[0])
    spec = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.asarray(x).dtype
                                       if not hasattr(x, "dtype") else x.dtype,
                                       sharding=one), tree)
    n_params = sum(x.size for x in jax.tree.leaves(shapes))
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    pool = sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves(eng.pool.caches))
    print(json.dumps({"layers": mcfg.num_layers, "parameters_m": n_params / 1e6,
                      "weights_gib": weights / GIB, "kv_pool_gib": pool / GIB}),
          flush=True)
    jax.default_backend = lambda: "tpu"
    dec_args = (eng._p_dec, eng.pool.caches, eng._last_logits, eng._rngs,
                eng._d_lengths, eng._d_temps, eng._d_top_ks, eng._d_top_ps,
                eng._d_reject, eng._d_masks)
    out = [report("decode", eng._decode.lower(*spec(dec_args), None,
                                              None).compile())]
    B = serving.prefill_max_batch
    padded = -(-mix["prompt"]["max"] // serving.prefill_bucket) \
        * serving.prefill_bucket
    pre_args = (eng._p_dec, eng.pool.caches, eng._last_logits, eng._rngs,
                np.zeros((B, padded), np.int32), np.zeros((B,), np.int32),
                np.zeros((B,), np.int32), np.zeros((B, 2), np.uint32))
    out.append(report(f"prefill[{B}x{padded}]",
                      eng._prefill.lower(*spec(pre_args), None,
                                         None).compile()))
    jax.default_backend = real_backend
    eng.close()
    return out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--layers", type=int, default=None,
                   help="try another depth than the configuration's")
    args = p.parse_args()
    sys.path.insert(0, ROOT)
    from benchmark import run as bench_run
    _, cell, config, traffic = bench_run.load_cell(args.workload)

    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    if traffic["driver"] == "train_job":
        jax.default_backend = lambda: "tpu"     # see the module docstring
    if traffic["driver"] == "train_job":
        fit_train(config, traffic, cell["chips"], args.layers, topo)
    elif traffic["driver"] == "serve_open_loop":
        fit_serve(config, traffic, args.layers, topo)
    else:
        raise SystemExit(f"no fit rehearsal for driver {traffic['driver']!r}")


if __name__ == "__main__":
    main()
