"""`chip_smoke.py` and the compile cache it places, as far as a CPU host
can check them: the smoke refuses to run without a TPU, its four-chip
phase is right on four virtual devices, its counter check tells the
engines apart, and the persistent compile cache lands where
``JAX_COMPILATION_CACHE_DIR`` says or else in ``<checkout>/.jax_cache``.
The served-path phase is rehearsed in test_batch_dataplane.py."""

import os
import pathlib
import subprocess
import sys

import pytest

import chip_smoke

REPO = pathlib.Path(__file__).resolve().parent.parent


def _run(code_or_script, env_extra, *args):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run([sys.executable, *code_or_script, *args],
                          cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=300)


def test_chip_smoke_refuses_to_run_without_a_tpu():
    for argv in ([], ["--multichip"]):
        out = _run(["chip_smoke.py"], {}, *argv)
        assert out.returncode != 0
        assert '"ok": true' not in out.stdout
        assert out.stdout.strip() == ""        # nothing was started
        assert "no TPU" in out.stderr


def test_multichip_phase_on_virtual_devices(capsys):
    """The --multichip phase on the suite's virtual CPU devices: mesh
    engine bit-exact against the single-device codec, every array on
    four devices, and the (data=1, shard=4) layout it reports."""
    import json

    chip_smoke.phase_multichip(seed=5, batch_mib=1)
    rows = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert rows[0]["mesh_shape"] == {"data": 1, "shard": 4}
    arrays = {r["array"]: r for r in rows if "array" in r}
    assert set(arrays) == {"encode_in", "encode_out", "decode_in",
                           "decode_out"}
    assert all(r["devices"] == 4 for r in arrays.values())
    # the stripe batch is replicated; only the chunk axis is split
    assert arrays["encode_in"]["per_device_shapes"] == [[32, 8, 4096]] * 4
    assert arrays["decode_in"]["per_device_shapes"] == [[32, 3, 4096]] * 4
    assert rows[-1] == {"check": "mesh_ec_engine",
                        "batch_bytes": 1 << 20, "ok": True}


_PALLAS_RAN = {"planar_matmul_calls": 10, "planar_matmul_bytes": 1 << 20,
               "planar_stack_groups": 80, "ec_coalesced_ticks": 3,
               "ec_tick_crc_device_ticks": 3}


@pytest.mark.parametrize("change,complaint", [
    ({}, None),
    ({"planar_matmul_calls": 0}, "no planar matmul"),
    ({"planar_matmul_bytes": 1000}, "far below"),
    ({"ec_host_matmul_calls": 1}, "host GF engine"),
    ({"ec_host_planar_matmul_calls": 2}, "host GF engine"),
    ({"ec_coalesced_ticks": 0}, "no coalesced"),
    ({"planar_stack_groups": 10}, "stack-group"),
    ({"ec_tick_crc_device_ticks": 0}, "chunk-crc program"),
])
def test_counter_check_tells_the_engines_apart(change, complaint):
    report = {"counters": {**_PALLAS_RAN, **change},
              "bytes_written": 1 << 20}
    if complaint is None:
        chip_smoke.check_device_did_the_work(report)
    else:
        with pytest.raises(AssertionError, match=complaint):
            chip_smoke.check_device_did_the_work(report)


_JIT_ONCE = """
import os, sys, jax, jax.numpy as jnp
from ceph_tpu.utils import compile_cache
path = compile_cache.enable()
jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()
print(path)
print(jax.config.jax_compilation_cache_dir)
"""


def test_compile_cache_goes_where_the_variable_says(tmp_path):
    placed = tmp_path / "placed"
    out = _run(["-c", _JIT_ONCE], {"JAX_COMPILATION_CACHE_DIR": str(placed)})
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [str(placed)] * 2
    assert any(placed.iterdir()), "no cache entry was written there"


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    from ceph_tpu.utils import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.cache_dir() == str(REPO / ".jax_cache")
    out = _run(["-c", _JIT_ONCE], {})
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [str(REPO / ".jax_cache")] * 2
