"""Inference tests: sampling, KV-cache decode, generation, beam search, server.

Contracts from the reference's inference stack (SURVEY.md §2.6):
- greedy KV-cache decode must equal argmax over full-context forwards
  (the KV cache is an optimization, not a semantics change);
- top-k/top-p filtering semantics (ref: sampling.py:14-93);
- server /api payload contract (ref: text_generation_server.py:31-228).
"""
import json
import threading
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_tpu.config import ModelConfig
from megatron_tpu.inference import (Generator, SamplingParams, beam_search,
                                    sample)
from megatron_tpu.models import language_model as lm


@pytest.fixture(scope="module")
def tiny_model():
    cfg = ModelConfig(num_layers=2, hidden_size=64, num_attention_heads=4,
                      num_kv_heads=2, vocab_size=96, seq_length=64,
                      make_vocab_size_divisible_by=32,
                      compute_dtype="float32").derived()
    params = lm.model_init(jax.random.PRNGKey(0), cfg)
    return params, cfg


class TestSampling:
    def test_top_k(self):
        logits = jnp.asarray([[1.0, 5.0, 3.0, 2.0]])
        for _ in range(5):
            t = sample(jax.random.PRNGKey(_), logits, top_k=2,
                       temperature=1.0)
            assert int(t[0]) in (1, 2)

    def test_top_p(self):
        # one dominant token: nucleus p=0.5 keeps only it
        logits = jnp.asarray([[10.0, 0.0, 0.0, 0.0]])
        for s in range(5):
            t = sample(jax.random.PRNGKey(s), logits, top_p=0.5)
            assert int(t[0]) == 0

    def test_greedy(self):
        logits = jnp.asarray([[1.0, 5.0, 3.0]])
        t = sample(jax.random.PRNGKey(0), logits, temperature=0.0)
        assert int(t[0]) == 1

    def test_vocab_mask(self):
        logits = jnp.asarray([[0.0, 1.0, 100.0]])
        t = sample(jax.random.PRNGKey(0), logits, temperature=0.0,
                   vocab_size=2)
        assert int(t[0]) == 1


class TestGeneration:
    def test_greedy_decode_matches_full_forward(self, tiny_model):
        """KV-cache incremental decode == repeated full forwards (greedy)."""
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        prompt = [5, 17, 3, 42]
        max_new = 8
        tokens, lengths, _ = gen.generate(
            [prompt], max_new, sampling=SamplingParams(temperature=0.0))

        # oracle: argmax over full-context forwards, no cache
        rope = lm.make_rope(cfg)
        seq = list(prompt)
        for _ in range(max_new):
            logits, _ = lm.model_forward(
                params, jnp.asarray([seq]), cfg, rope=rope,
                logits_dtype=jnp.float32)
            nxt = int(jnp.argmax(logits[0, -1, :cfg.vocab_size]))
            seq.append(nxt)
            if nxt == 0:
                break
        want = np.asarray(seq)
        got = np.asarray(tokens[0, :len(seq)])
        np.testing.assert_array_equal(got, want)

    def test_greedy_decode_matches_full_forward_flash_prefill(
            self, tiny_model):
        """Same oracle check with attention_impl='flash': the prefill then
        takes the flash path on the raw k/v (offset-0 prefill == plain
        causal attention — models/attention.py prefill_flash) while decode
        steps stay on the cached dot path."""
        import dataclasses as dc
        params, cfg = tiny_model
        cfg = dc.replace(cfg, attention_impl="flash")
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        # >=16 tokens: Generator rounds the prefill length down to a
        # multiple of 16, and the flash-prefill gate needs s > 1 — a
        # short prompt would prefill at s=1 and test nothing new
        prompt = [(7 * i + 3) % 90 + 1 for i in range(20)]
        max_new = 8
        tokens, lengths, _ = gen.generate(
            [prompt], max_new, sampling=SamplingParams(temperature=0.0))
        rope = lm.make_rope(cfg)
        seq = list(prompt)
        for _ in range(max_new):
            logits, _ = lm.model_forward(
                params, jnp.asarray([seq]), cfg, rope=rope,
                logits_dtype=jnp.float32)
            nxt = int(jnp.argmax(logits[0, -1, :cfg.vocab_size]))
            seq.append(nxt)
            if nxt == 0:
                break
        np.testing.assert_array_equal(
            np.asarray(tokens[0, :len(seq)]), np.asarray(seq))

    def test_batch_mixed_lengths(self, tiny_model):
        """Rows with different prompt lengths keep their prompt tokens
        (ref: generation.py:210-214)."""
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        prompts = [[5, 6, 7], [11, 12, 13, 14, 15, 16]]
        tokens, lengths, _ = gen.generate(
            prompts, 4, sampling=SamplingParams(temperature=0.0))
        for i, p in enumerate(prompts):
            np.testing.assert_array_equal(tokens[i, :len(p)], p)
        assert all(lengths[i] > len(p) for i, p in enumerate(prompts))

    def test_score(self, tiny_model):
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        rows = [[5, 6, 7, 8], [9, 10, 11]]
        lps = gen.score(rows)
        assert lps.shape == (2, 3)
        assert np.all(lps[0] <= 0)

    @pytest.mark.slow  # convergence/training-loop test
    def test_beam_search_beats_greedy(self, tiny_model):
        """Beam-1 == greedy; wider beams score >= beam-1."""
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        prompt = [5, 17, 3]
        t1, l1, s1 = beam_search(gen, prompt, 1, 6)
        t4, l4, s4 = beam_search(gen, prompt, 4, 6)
        greedy, gl, _ = gen.generate([prompt], 6,
                                     sampling=SamplingParams(temperature=0.0))
        np.testing.assert_array_equal(t1[0, :l1[0]], greedy[0, :gl[0]])
        assert s4[0] >= s1[0] - 1e-5


class FakeTokenizer:
    vocab_size = 96
    eod = 0
    bos = 1

    def tokenize(self, text):
        return [2 + (ord(c) % 90) for c in text][:16]

    def detokenize(self, ids):
        return " ".join(str(i) for i in ids)


class TestServer:
    def test_http_server_contract(self, tiny_model):
        from megatron_tpu.inference.server import MegatronServer
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        server = MegatronServer(gen, FakeTokenizer())

        # direct handler contract: (status, body)
        status, out = server.handle({"prompts": ["hello"],
                                     "tokens_to_generate": 4,
                                     "temperature": 0.0, "logprobs": True})
        assert status == 200
        assert "text" in out and "segments" in out and "logprobs" in out
        status, out = server.handle({})
        assert status == 400
        assert out["message"] == "prompts argument required"

        # over HTTP (stdlib backend)
        import socket
        from http.server import ThreadingHTTPServer
        sock = socket.socket()
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]
        sock.close()
        t = threading.Thread(target=server._run_stdlib,
                             args=("127.0.0.1", port), daemon=True)
        t.start()
        import time
        data = None
        for _ in range(50):
            try:
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/api",
                    data=json.dumps({"prompts": ["hi"],
                                     "tokens_to_generate": 2,
                                     "temperature": 0.0}).encode(),
                    method="PUT",
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=10) as resp:
                    data = json.loads(resp.read())
                break
            except (ConnectionError, urllib.error.URLError):
                time.sleep(0.2)
        assert data is not None, "server never became reachable"
        assert "text" in data and len(data["text"]) == 1


class TestShardedGeneration:
    """VERDICT round-1 item 8: serving a TP-sharded model. Decode on a
    tp=2 (x pp=2) mesh must emit exactly the same tokens as single-device
    decode, with params consumed in their sharded layout."""

    def _mesh(self, dp, pp, tp):
        from conftest import make_test_mesh
        return make_test_mesh(jax.devices(), dp=dp, pp=pp, tp=tp)

    @pytest.mark.parametrize("pp,tp", [(1, 2), (2, 2)])
    def test_tp_sharded_decode_equals_single_device(self, tiny_model, pp, tp):
        params, cfg = tiny_model
        prompts = [[5, 6, 7, 8], [9, 10, 11]]
        greedy = SamplingParams(top_k=1, temperature=1.0)

        gen0 = Generator(params, cfg, eos_id=0, pad_id=0)
        want_toks, want_lens, _ = gen0.generate(prompts, max_new_tokens=8,
                                                sampling=greedy, seed=0)

        mesh = self._mesh(1, pp, tp)
        from megatron_tpu.parallel import sharding as shd
        rules = shd.make_logical_rules(False)
        sharded_params = jax.device_put(
            params, shd.tree_logical_to_sharding(
                mesh, lm.model_axes(cfg), rules))
        with jax.set_mesh(mesh):
            gen = Generator(sharded_params, cfg, eos_id=0, pad_id=0,
                            mesh=mesh)
            got_toks, got_lens, _ = gen.generate(prompts, max_new_tokens=8,
                                                 sampling=greedy, seed=0)
        np.testing.assert_array_equal(got_lens, want_lens)
        np.testing.assert_array_equal(got_toks, want_toks)

    def test_sharded_score_matches(self, tiny_model):
        params, cfg = tiny_model
        rows = [[3, 4, 5, 6, 7], [8, 9, 10]]
        gen0 = Generator(params, cfg, eos_id=0, pad_id=0)
        want = gen0.score(rows)
        mesh = self._mesh(1, 1, 2)
        from megatron_tpu.parallel import sharding as shd
        rules = shd.make_logical_rules(False)
        sharded_params = jax.device_put(
            params, shd.tree_logical_to_sharding(
                mesh, lm.model_axes(cfg), rules))
        with jax.set_mesh(mesh):
            gen = Generator(sharded_params, cfg, eos_id=0, pad_id=0,
                            mesh=mesh)
            got = gen.score(rows)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


class TestRollingKVCache:
    """Mistral-style rolling-buffer serving: with --sliding_window W the
    cache holds exactly W slots (init_kv_caches), writes land at
    position % W, and the slot->position map masks reads. The contract:
    token-for-token equality with the SAME windowed model on a
    full-length cache."""

    def _model(self, window, impl="dot"):
        cfg = ModelConfig(num_layers=2, hidden_size=64,
                          num_attention_heads=4, num_kv_heads=2,
                          vocab_size=96, seq_length=256,
                          max_position_embeddings=256,
                          make_vocab_size_divisible_by=32,
                          sliding_window=window, attention_impl=impl,
                          compute_dtype="float32").derived()
        params = lm.model_init(jax.random.PRNGKey(0), cfg)
        return params, cfg

    @pytest.mark.parametrize("impl", ["dot", "flash"])
    def test_rolling_equals_full_cache(self, impl):
        """Greedy decode past the window boundary: the rolling W-slot
        cache must reproduce the full-cache outputs exactly (positions
        the band can see are bit-identical; everything else is masked in
        both layouts). Prompt 24 + 40 new tokens crosses window=32."""
        window = 32
        params, cfg = self._model(window, impl)
        prompt = list(np.random.RandomState(0).randint(1, 96, 24))
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        toks, _, lp = gen.generate(
            [prompt], 40, sampling=SamplingParams(temperature=0.0))
        assert np.isfinite(np.asarray(lp)).all()
        outs = {"rolling": np.asarray(toks)}

        # oracle: no-cache full forwards with the banded mask — the
        # positions inside the band see bit-identical k/v in both
        # layouts, everything outside is masked in both
        rope = lm.make_rope(cfg)
        seq = list(prompt)
        for _ in range(40):
            logits, _ = lm.model_forward(params, jnp.asarray([seq]), cfg,
                                         rope=rope)
            nxt = int(jnp.argmax(logits[0, -1, :cfg.vocab_size]))
            seq.append(nxt)
            if nxt == 0:
                break
        want = np.asarray(seq)
        got = np.asarray(outs["rolling"][0, :len(seq)])
        np.testing.assert_array_equal(got, want)

    def test_rolling_cache_is_window_sized(self):
        from megatron_tpu.inference.generation import init_kv_caches
        _, cfg = self._model(32, impl="flash")
        c = init_kv_caches(cfg, 1, 256)
        assert c.k.shape[2] == 32  # [L, b, W, nkv, hd]

    def test_dot_impl_long_prompt_keeps_full_cache(self):
        """A dot-impl prompt LONGER than the window cannot prefill a
        W-slot buffer (its own writes would evict history mid-chunk) —
        init_kv_caches must keep the full-length cache and generation
        must still match the banded no-cache oracle."""
        from megatron_tpu.inference.generation import init_kv_caches
        params, cfg = self._model(32, impl="dot")
        c = init_kv_caches(cfg, 1, 256, prefill_len=48)
        assert c.k.shape[2] == 256  # NOT clamped
        prompt = list(np.random.RandomState(1).randint(1, 96, 48))
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        toks, _, _ = gen.generate(
            [prompt], 8, sampling=SamplingParams(temperature=0.0))
        rope = lm.make_rope(cfg)
        seq = list(prompt)
        for _ in range(8):
            logits, _ = lm.model_forward(params, jnp.asarray([seq]), cfg,
                                         rope=rope)
            nxt = int(jnp.argmax(logits[0, -1, :cfg.vocab_size]))
            seq.append(nxt)
            if nxt == 0:
                break
        np.testing.assert_array_equal(np.asarray(toks[0, :len(seq)]),
                                      np.asarray(seq))

    def test_beam_search_with_rolling_cache(self):
        """Beam search prefills through init_kv_caches(prefill_len=...):
        the rolling buffer must engage (window-sized) and the parent
        reindex must gather ring slots consistently — finite scores and
        in-vocab beams past the window boundary."""
        params, cfg = self._model(32, impl="flash")
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        prompt = list(np.random.RandomState(2).randint(1, 96, 12))
        toks, out_len, scores = beam_search(gen, prompt, beam_width=2,
                                            max_new_tokens=36)
        assert np.isfinite(np.asarray(scores)).all()
        assert (np.asarray(toks) < 96).all()
        # beam_width=1 greedy-equivalence: the rolling reindex must not
        # corrupt the single surviving beam — it must match generate()'s
        # greedy output exactly (the real reindex-consistency check)
        toks1, _, _ = beam_search(gen, prompt, beam_width=1,
                                  max_new_tokens=36)
        greedy, lens, _ = gen.generate(
            [prompt], 36, sampling=SamplingParams(temperature=0.0))
        n = int(lens[0])
        np.testing.assert_array_equal(np.asarray(toks1)[0, :n],
                                      np.asarray(greedy)[0, :n])

    def test_rolling_flash_prefill_poisons_offset_gt_0(self):
        """The rolling flash prefill is defined ONLY at offset 0 (a
        mid-stream multi-token chunk would need history the W-slot
        buffer already evicted). The guard poisons such a call with NaN
        so a contract violation fails at the first logit instead of
        silently decoding garbage — and stays finite at offset 0."""
        from megatron_tpu.models.attention import (KVCache, attention_apply,
                                                   attention_init)
        _, cfg = self._model(32, impl="flash")
        acfg = cfg
        p = attention_init(jax.random.PRNGKey(0), acfg)
        rope = lm.make_rope(acfg)
        x = jnp.asarray(np.random.RandomState(4).randn(1, 8, 64), jnp.float32)
        for offset, finite in ((0, True), (16, False)):
            cache = KVCache(
                k=jnp.zeros((1, 1, 32, 2, 16), jnp.bfloat16),
                v=jnp.zeros((1, 1, 32, 2, 16), jnp.bfloat16),
                offset=jnp.asarray([offset], jnp.int32))
            y, _ = attention_apply(p, x, acfg, rope_cos=rope.cos,
                                   rope_sin=rope.sin, kv_cache=cache,
                                   cache_layer=0)
            assert bool(np.isfinite(np.asarray(y)).all()) is finite, offset

    @pytest.mark.parametrize("delta,dot_cap", [(-1, 32), (0, 32),
                                               (1, 256)])
    def test_window_boundary_cap_selection(self, delta, dot_cap):
        """prefill_len one below / exactly at / one above the window:
        a dot-impl prefill that FITS the W-slot buffer rolls (cap W);
        one token over keeps the full-length cache (its own writes
        would evict history mid-chunk). The flash impl always rolls
        (prefill outputs come from the raw k/v)."""
        from megatron_tpu.inference.generation import init_kv_caches
        _, cfg = self._model(32, impl="dot")
        c = init_kv_caches(cfg, 1, 256, prefill_len=32 + delta)
        assert c.k.shape[2] == dot_cap, delta
        _, cfgf = self._model(32, impl="flash")
        cf = init_kv_caches(cfgf, 1, 256, prefill_len=32 + delta)
        assert cf.k.shape[2] == 32, delta

    @pytest.mark.parametrize("delta", [-1, 0, 1])
    def test_window_boundary_outputs_match_banded_oracle(self, delta):
        """Greedy decode with prefill_len W-1 / W / W+1 must match the
        banded NO-CACHE oracle token-for-token whichever cache layout
        (rolling W-slot vs full buffer) the boundary selects."""
        params, cfg = self._model(32, impl="dot")
        plen = 32 + delta
        prompt = list(np.random.RandomState(10 + delta).randint(
            1, 96, plen))
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        toks, _, lp = gen.generate(
            [prompt], 8, sampling=SamplingParams(temperature=0.0))
        assert np.isfinite(np.asarray(lp)).all()
        rope = lm.make_rope(cfg)
        seq = list(prompt)
        for _ in range(8):
            logits, _ = lm.model_forward(params, jnp.asarray([seq]), cfg,
                                         rope=rope)
            nxt = int(jnp.argmax(logits[0, -1, :cfg.vocab_size]))
            seq.append(nxt)
            if nxt == 0:
                break
        np.testing.assert_array_equal(np.asarray(toks[0, :len(seq)]),
                                      np.asarray(seq), err_msg=str(delta))

    def test_below_window_equals_non_windowed_cache(self):
        """Total length <= W: the band covers all history, so the
        windowed model on its ROLLING cache must equal the NON-windowed
        model on its full cache bit-for-bit (same params — init depends
        only on shapes)."""
        import dataclasses as dc
        params, cfg = self._model(32, impl="dot")
        cfg_full = dc.replace(cfg, sliding_window=None)
        prompt = list(np.random.RandomState(20).randint(1, 96, 31))
        out = {}
        for name, c in (("rolling", cfg), ("full", cfg_full)):
            gen = Generator(params, c, eos_id=0, pad_id=0)
            toks, lens, _ = gen.generate(
                [prompt], 1, sampling=SamplingParams(temperature=0.0))
            out[name] = np.asarray(toks[0, :lens[0]])
        np.testing.assert_array_equal(out["rolling"], out["full"])

    def test_rolling_with_int8_cache(self):
        """Rolling + int8 quantized cache compose: finite outputs and
        window-sized int8 buffers with scales."""
        params, cfg = self._model(32)
        gen = Generator(params, cfg, eos_id=0, pad_id=0,
                        kv_cache_dtype=jnp.int8)
        toks, lens, lp = gen.generate(
            [[5, 17, 3, 42]], 40, sampling=SamplingParams(temperature=0.0))
        assert np.isfinite(np.asarray(lp)).all()
        # non-degenerate decode past the window: in-vocab, varied tokens
        gen_region = np.asarray(toks)[0, 4:int(lens[0])]
        assert (gen_region < 96).all() and (gen_region >= 0).all()
        assert len(set(gen_region.tolist())) > 2, gen_region


@pytest.mark.slow
class TestShardedRollingCache:
    def test_tp2_rolling_decode_matches_single(self, devices):
        """The rolling W-slot cache under tp sharding (kv-heads split over
        'tp', ring slots on the unsharded axis): greedy output equals the
        single-device rolling run token-for-token."""
        from megatron_tpu.config import ParallelConfig
        from megatron_tpu.parallel.mesh import build_mesh
        # one source of truth for the windowed model config
        params, cfg = TestRollingKVCache()._model(32, impl="flash")
        prompt = list(np.random.RandomState(3).randint(1, 96, 24))
        outs = {}
        for tp in (1, 2):
            mesh = build_mesh(ParallelConfig(tensor_parallel=tp),
                              devices=jax.devices()[:tp])
            gen = Generator(params, cfg, eos_id=0, pad_id=0, mesh=mesh)
            toks, _, _ = gen.generate(
                [prompt], 40, sampling=SamplingParams(temperature=0.0))
            outs[tp] = np.asarray(toks)
        np.testing.assert_array_equal(outs[2], outs[1])
