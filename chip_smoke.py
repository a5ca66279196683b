"""Run the store-backed served retrieval path once on one TPU and check it.

    python chip_smoke.py

Hurricane ISABEL velocity scale: three seeded 100x500x500 float64 fields
(Vx, Vy, Vz; the SDRBench shape, padded to 129x513x513 by the transform)
are refactored into a fresh ``.prs`` archive on every run, so the encode
kernel runs on the chip each time; then one sticky client tightens VTOT
requests through tau = 1e-2, 1e-4, 1e-6 via ``RetrievalServer.submit``
while a second, one wave behind, asks 1e-2 and 1e-4 (five requests: the
sixth would push a cold run near the 1200 s limit), and every answer must
hold  true error <= certified bound <= tau_abs  against a float64 NumPy
VTOT, guaranteed and not degraded (``repro.launch.smoke``).

Everything runs in this one process: it starts no children, so it alone
holds the chip.  It exits non-zero, printing no result, when JAX finds no
TPU or the repository's ``src/`` is not beside this file.  The last line of
a passing run is ``{"ok": true, "device": {...}}``.  The persistent
compilation cache follows ``JAX_COMPILATION_CACHE_DIR`` and otherwise sits
at ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SHAPE = (100, 500, 500)


def main() -> int:
    t_start = time.perf_counter()
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no src/repro beside {__file__}", file=sys.stderr)
        return 1
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r} devices)",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import use_checkout_cache
    from repro.kernels.bitplane_pack import interpret_default
    from repro.launch.smoke import serve_and_check
    if interpret_default():
        print("chip_smoke: Pallas kernels would run interpreted",
              file=sys.stderr)
        return 1
    cache = use_checkout_cache()
    print(f"[chip] {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache {cache}", flush=True)

    scratch = ROOT / ".smoke"
    scratch.mkdir(exist_ok=True)
    workdir = tempfile.mkdtemp(prefix="run-", dir=scratch)
    try:
        report = serve_and_check(SHAPE, workdir,
                                 log=lambda s: print(s, flush=True))
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    stats = dev.memory_stats() or {}
    print(f"[chip] setup: generate {report.generate_s:.3f}s, refactor+save+"
          f"open {report.refactor_s:.3f}s; compile {report.compile_s:.3f}s "
          f"(trace+lower+backend, summed); wall "
          f"{time.perf_counter() - t_start:.3f}s", flush=True)
    print(f"[chip] decode batcher: {report.batch_stats}", flush=True)
    print(f"[chip] peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
          f"bytes_limit={stats.get('bytes_limit')}", flush=True)
    if not report.ok:
        bad = [a for a in report.answers if not a.ok]
        print(f"chip_smoke: {len(bad)} of {len(report.answers)} answers "
              f"failed the check", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
