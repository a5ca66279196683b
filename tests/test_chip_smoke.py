"""chip_smoke.py's serve-and-check phase on the CPU at a tiny shape, and the
smoke's refusals: no TPU, or no repository beside it, means a non-zero exit
and no result line."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax

from repro import compile_cache
from repro.launch import smoke

ROOT = Path(__file__).resolve().parents[1]


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src")] + [p for p in [env.get("PYTHONPATH")] if p])
    return env


def test_serve_and_check_tiny_shape_matches_numpy(tmp_path):
    report = smoke.serve_and_check((17, 33, 33), str(tmp_path),
                                   log=lambda s: None)
    assert report.ok
    assert len(report.answers) == 2 * len(smoke.TAUS) - 1
    for a in report.answers:
        assert a.guaranteed and not a.degraded
        assert a.true_error <= a.bound <= a.tau_abs
        assert a.bytes_moved > 0
    # each client tightened incrementally: the lagging client repeats the
    # leader's steps and moves the same bytes per step
    by_client = {}
    for a in report.answers:
        by_client.setdefault(a.client, []).append((a.tau, a.bytes_moved))
    assert [t for t, _ in by_client["client0"]] == list(smoke.TAUS)
    assert by_client["client1"] == by_client["client0"][:2]
    assert report.batch_stats["decode_items"] > 0


def test_serve_and_check_refuses_an_existing_archive(tmp_path):
    (tmp_path / "velocity.prs").write_bytes(b"")
    with pytest.raises(FileExistsError):
        smoke.serve_and_check((5, 9, 9), str(tmp_path), log=lambda s: None)


def test_schedule_staggers_clients():
    waves = smoke.schedule(["a", "b"], (1e-2, 1e-4, 1e-6))
    assert waves == [[("a", 1e-2)],
                     [("a", 1e-4), ("b", 1e-2)],
                     [("a", 1e-6), ("b", 1e-4)]]


def test_vtot_reference():
    f = {"Vx": np.array([3.0, 0.0]), "Vy": np.array([4.0, 0.0]),
         "Vz": np.array([0.0, -2.0])}
    np.testing.assert_array_equal(smoke.vtot(f), [5.0, 2.0])


def test_chip_smoke_exits_nonzero_on_cpu():
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=_cpu_env(), capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no TPU" in out.stderr


def test_chip_smoke_alone_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


def test_httpd_never_initializes_a_jax_backend(tmp_path):
    """The README runs the store's HTTP server beside the serving process;
    it must not claim the chip."""
    (tmp_path / "x.seg").write_bytes(b"abc")
    code = (
        "import urllib.request\n"
        "import repro.store.httpd as h\n"
        "import jax._src.xla_bridge as xb\n"
        f"srv = h.StoreHTTPServer({str(tmp_path)!r}, port=0).start()\n"
        "assert urllib.request.urlopen(srv.url + '/x.seg').read() == b'abc'\n"
        "srv.stop()\n"
        "assert not xb._backends, xb._backends\n")
    out = subprocess.run([sys.executable, "-c", code], env=_cpu_env(),
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr


def test_compile_cache_follows_env_else_checkout(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.use_checkout_cache() == "/elsewhere"
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.use_checkout_cache()
    assert path == str(ROOT / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", path)]


def test_no_kernel_defaults_to_interpret():
    import inspect
    from repro.kernels import (bitplane_pack, bitplane_unpack, hier_level,
                               qoi_vtotal)
    for fn in (bitplane_pack.bitplane_pack, bitplane_unpack.bitplane_unpack,
               hier_level.hier_level_surplus, qoi_vtotal.qoi_vtotal_fused):
        sig = inspect.signature(inspect.unwrap(fn))
        assert sig.parameters["interpret"].default is None, fn
