"""Convert a HuggingFace Llama/Falcon checkpoint into a megatron_tpu release
checkpoint, and export back.

TPU-native port of the reference's conversion entry points
(ref: weights2megatron/weights2megatron.py:148 main,
weights2megatron/megatron2hf.py, tools/checkpoint_util.py). The reference
needs THREE tools (hf->megatron, megatron->hf, and an offline tp/pp
resharder); here there is one layout-free checkpoint, so resharding is a
load-time no-op and this tool only moves weights across formats.

  python tools/convert_hf_checkpoint.py import --hf_path X --out ckpts/llama7b \
      --family llama --size 7b
  python tools/convert_hf_checkpoint.py export --load ckpts/llama7b --hf_out Y \
      --family llama --size 7b
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from megatron_tpu.utils.compile_cache import ensure_compile_cache
ensure_compile_cache()


def _model_cfg(family: str, size: str):
    from megatron_tpu.config import (falcon_config, llama2_config,
                                     mixtral_config)
    if family == "llama":
        return llama2_config(size)
    if family == "falcon":
        return falcon_config(size)
    if family == "mixtral":
        return mixtral_config(size)
    raise ValueError(f"unknown family {family}")


def do_import(args):
    import numpy as np

    from megatron_tpu.config import MegatronConfig
    from megatron_tpu.convert import hf_falcon_to_params, hf_llama_to_params
    from megatron_tpu.training.checkpointing import save_checkpoint
    from megatron_tpu.training.train_step import TrainState

    if args.source == "megatron":
        # reference mp_rank layout (iter_N/mp_rank_XX[_YYY]/
        # model_optim_rng.pt) — tp/pp/vpp shards merged, arch read from
        # the embedded args namespace (ref: megatron/checkpointing.py)
        from megatron_tpu.convert.megatron import (config_from_megatron_args,
                                                   load_megatron_checkpoint,
                                                   megatron_to_params)
        print(f"loading reference-megatron checkpoint from {args.hf_path}")
        sd, ref_args, meta = load_megatron_checkpoint(args.hf_path)
        print(f"  iteration={meta['iteration']} version="
              f"{meta['checkpoint_version']} tp={meta['tp']} pp={meta['pp']}")
        mcfg = config_from_megatron_args(ref_args)
        params = megatron_to_params(sd, mcfg, dtype=np.float32)
        state = TrainState(params=params, opt_state=None, iteration=0)
        cfg = MegatronConfig(model=mcfg)
        d = save_checkpoint(args.out, state, cfg, iteration=0, release=True)
        print(f"wrote release checkpoint {d}")
        return

    mcfg = _model_cfg(args.family, args.size)
    if args.source == "meta":
        # raw consolidated.NN.pth shards: merge then map, no rotary permute
        # (ref: weights2megatron/merge_llama.py:117 merge_llama dispatch)
        from megatron_tpu.convert import (merge_meta_llama,
                                          meta_llama_to_params)
        assert args.family == "llama", "meta format is llama-only"
        print(f"merging meta shards from {args.hf_path}")
        sd = merge_meta_llama(args.hf_path)
        params = meta_llama_to_params(sd, mcfg, dtype=np.float32)
    else:
        import torch
        from transformers import AutoModelForCausalLM
        print(f"loading HF model from {args.hf_path}")
        model = AutoModelForCausalLM.from_pretrained(
            args.hf_path, torch_dtype=torch.float32)
        sd = {k: v.detach().cpu().numpy()
              for k, v in model.state_dict().items()}
        del model
        from megatron_tpu.convert import hf_mixtral_to_params
        conv = {"llama": hf_llama_to_params,
                "falcon": hf_falcon_to_params,
                "mixtral": hf_mixtral_to_params}[args.family]
        params = conv(sd, mcfg, dtype=np.float32)
    state = TrainState(params=params, opt_state=None, iteration=0)
    cfg = MegatronConfig(model=mcfg)
    d = save_checkpoint(args.out, state, cfg, iteration=0, release=True)
    print(f"wrote release checkpoint {d}")


def do_export(args):
    from megatron_tpu.models import language_model as lm
    from megatron_tpu.training import checkpointing as ckpt
    from megatron_tpu.training.train_step import TrainState
    import jax

    # architecture comes from the checkpoint's embedded config.json when
    # present (finetune may have overridden vocab_size etc.); the
    # --family/--size preset is only the fallback
    saved_cfg = ckpt.load_config_from_checkpoint(args.load)
    mcfg = (saved_cfg.model if saved_cfg is not None
            else _model_cfg(args.family, args.size))
    example = TrainState(
        params=jax.eval_shape(
            lambda: lm.model_init(jax.random.PRNGKey(0), mcfg)),
        opt_state=None, iteration=0)
    state, _, _ = ckpt.load_checkpoint(args.load, example, no_load_optim=True)
    assert state is not None, f"no checkpoint under {args.load}"
    os.makedirs(args.hf_out, exist_ok=True)
    import torch
    if args.family == "llama":
        from megatron_tpu.convert import params_to_hf_llama
        from transformers import LlamaConfig
        sd = params_to_hf_llama(state.params, mcfg)
        hf_cfg = LlamaConfig(
            vocab_size=mcfg.vocab_size, hidden_size=mcfg.hidden_size,
            num_hidden_layers=mcfg.num_layers,
            num_attention_heads=mcfg.num_attention_heads,
            num_key_value_heads=mcfg.num_kv_heads,
            intermediate_size=mcfg.ffn_hidden_size,
            max_position_embeddings=mcfg.max_position_embeddings,
            rms_norm_eps=mcfg.norm_epsilon,
            tie_word_embeddings=mcfg.tie_embed_logits,
        )
    elif args.family == "mixtral":
        from megatron_tpu.convert import params_to_hf_mixtral
        from transformers import MixtralConfig
        sd = params_to_hf_mixtral(state.params, mcfg)
        hf_cfg = MixtralConfig(
            vocab_size=mcfg.vocab_size, hidden_size=mcfg.hidden_size,
            num_hidden_layers=mcfg.num_layers,
            num_attention_heads=mcfg.num_attention_heads,
            num_key_value_heads=mcfg.num_kv_heads,
            intermediate_size=mcfg.ffn_hidden_size,
            max_position_embeddings=mcfg.max_position_embeddings,
            rms_norm_eps=mcfg.norm_epsilon, rope_theta=mcfg.rope_theta,
            num_local_experts=mcfg.num_experts,
            num_experts_per_tok=mcfg.moe_top_k,
            tie_word_embeddings=mcfg.tie_embed_logits,
        )
    else:
        from megatron_tpu.convert import params_to_hf_falcon
        from transformers import FalconConfig
        sd = params_to_hf_falcon(state.params, mcfg)
        hf_cfg = FalconConfig(
            vocab_size=mcfg.vocab_size, hidden_size=mcfg.hidden_size,
            num_hidden_layers=mcfg.num_layers,
            num_attention_heads=mcfg.num_attention_heads,
            num_kv_heads=mcfg.num_kv_heads,
            ffn_hidden_size=mcfg.ffn_hidden_size,
            max_position_embeddings=mcfg.max_position_embeddings,
            rope_theta=mcfg.rope_theta,
            new_decoder_architecture=mcfg.parallel_layernorm,
            multi_query=mcfg.num_kv_heads == 1,
            parallel_attn=mcfg.parallel_attn, bias=mcfg.use_bias,
            layer_norm_epsilon=mcfg.norm_epsilon,
        )
    torch.save({k: torch.tensor(v) for k, v in sd.items()},
               os.path.join(args.hf_out, "pytorch_model.bin"))
    hf_cfg.save_pretrained(args.hf_out)
    print(f"wrote HF checkpoint to {args.hf_out}")


def main(argv=None):
    p = argparse.ArgumentParser()
    sub = p.add_subparsers(dest="cmd", required=True)
    pi = sub.add_parser("import")
    pi.add_argument("--hf_path", required=True,
                    help="HF model path, or a dir of consolidated.NN.pth "
                         "shards with --source meta")
    pi.add_argument("--out", required=True)
    pi.add_argument("--family", default="llama",
                    choices=["llama", "falcon", "mixtral"])
    pi.add_argument("--size", default="7b")
    pi.add_argument("--source", default="hf",
                    choices=["hf", "meta", "megatron"],
                    help="meta = raw Meta-llama consolidated shards; "
                         "megatron = reference iter_N/mp_rank_XX layout "
                         "(tp/pp shards merged, arch from embedded args)")
    pe = sub.add_parser("export")
    pe.add_argument("--load", required=True)
    pe.add_argument("--hf_out", required=True)
    pe.add_argument("--family", default="llama",
                    choices=["llama", "falcon", "mixtral"])
    pe.add_argument("--size", default="7b")
    args = p.parse_args(argv)
    if args.cmd == "import":
        do_import(args)
    else:
        do_export(args)


if __name__ == "__main__":
    main()
