"""chip_smoke.py's contract, rehearsed on the CPU.

The driver reads the LAST line of the script's standard output and wants
exactly `{"ok": ..., "device": {"platform", "kind", "count"}}` — no key
added, no line after it. `--rehearse` drives every path, argument and
child of the one-chip run at a tiny size (kernels interpreted) and can
never print `"ok": true`, because the platform is not the chip.
"""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def test_rehearsal_last_line_is_the_contracts(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # the rehearsal is a one-device run; the suite's 8 virtual devices
    # would make the device phase report a count it did not ask for
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, SCRIPT, "--rehearse", "--out", str(tmp_path / "o")],
        env=env, cwd=ROOT, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, timeout=600)
    assert r.stdout.endswith("\n") and not r.stdout.endswith("\n\n")
    lines = r.stdout.splitlines()
    last = json.loads(lines[-1])   # nothing follows the verdict
    assert set(last) == {"ok", "device"}, last
    assert set(last["device"]) == {"platform", "kind", "count"}, last
    assert last["ok"] is False and r.returncode != 0
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    # every earlier line is a phase's JSON record, and every phase of
    # the one-chip plan ran and passed
    phases = [json.loads(line) for line in lines[:-1]]
    assert [p["phase"] for p in phases] == \
        ["device", "kernels", "train", "serve"], r.stdout
    failed = [p for p in phases if not p["ok"]]
    assert not failed, (failed, r.stderr[-4000:])
    train = phases[2]
    assert train["losses"][-1] < train["losses"][0]
    assert phases[3]["program_traces"]["decode"] == 1


def test_verdict_line_key_set():
    sys.path.insert(0, ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.remove(ROOT)
    fake = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
            "jax": "0.9.0", "seconds": 1.0}   # extras must not leak
    line = chip_smoke.verdict_line(True, fake)
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert list(json.loads(line)) == ["ok", "device"]
    assert json.loads(chip_smoke.verdict_line(False, {}))["ok"] is False
    # importing the script pulled in no JAX: its process never holds
    # the chip its children need
    assert "jax" not in chip_smoke.__dict__


def test_compile_cache_is_placed_from_outside(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: the helper sets nothing. Unset: the
    cache is <checkout>/.jax_cache, a fixed path."""
    import jax

    from megatron_tpu.utils import compile_cache
    seen = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: seen.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    compile_cache.ensure_compile_cache()
    assert seen == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    compile_cache.ensure_compile_cache()
    assert seen == [("jax_compilation_cache_dir",
                     os.path.join(ROOT, ".jax_cache"))]
