"""The control of the check that decides ``correct``, a fault planted in
the program, and the readings that set the check's limits.

The configuration of the benchmark's cell states its results as certified
tolerances on float32-valued fields (SDRBench's width), not as an
arithmetic precision: float32 rounding of a QoI lies below the tightest tau
the traffic asks, so a float32 copy of the reference would meet every
stated guarantee and cannot serve as the control.  The control instead
breaks one stated guarantee, the tolerance, in the way that would tempt a
later change: every request is answered from the cheaper state one decade
of tau looser (``FACTOR``), moving fewer bytes, while the answer is handed
back as the answer to the tau asked.

The planted fault is the stale state: each session's answers after its
first hand back the values of its first answer, while the reported bound
moves on with the tau asked.  Both are planted at the server's public
surface (``RetrievalServer.submit`` and the sessions' ``current``), so a
change to the program's internals cannot break them.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 \\
        --control-seeds 4,5,6 --stale-seeds 7,8,9 --seconds 15

runs, in one process, the program on each of ``--seeds``, the control on
each of ``--control-seeds`` and the stale fault on each of
``--stale-seeds`` (a short window each, at the cell's own size and load)
and prints every run's compared numbers.  The benchmark's own runs never
plant either.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from dataclasses import replace
from pathlib import Path

import numpy as np

FACTOR = 10.0
ROOT = Path(__file__).resolve().parents[1]


def plant_control(server) -> None:
    """Answer every request at ``FACTOR`` times its tau."""
    submit = server.submit
    server.submit = lambda req: submit(replace(req, tau=req.tau * FACTOR))


def plant_values(server, make) -> None:
    """From each session's second request on, its values are read through
    ``make(current, variables)``, which returns the replacement for the
    session's ``current``: a fault where an answer's values are read."""
    submit = server.submit

    def planted_submit(req):
        session = server.sessions.get(req.client)
        if session is not None and "current" not in vars(session):
            session.current = make(session.current,
                                   list(server.archive.shapes))
        return submit(req)
    server.submit = planted_submit


def plant_stale(server) -> None:
    """Each session's later answers keep the values of its first."""
    def make(current, variables):
        first = {}
        for v in variables:
            try:
                first[v] = np.array(current(v)[0], copy=True)
            except Exception:     # a variable this session never read
                pass
        return lambda v: (first[v] if v in first else current(v)[0],
                          current(v)[1])
    plant_values(server, make)


PLANTS = {"control": plant_control, "stale": plant_stale}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--stale-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    cell = harness.load_cell(args.workload)
    runs = [(int(s), kind) for kind, seeds in
            (("program", args.seeds), ("control", args.control_seeds),
             ("stale", args.stale_seeds))
            for s in seeds.split(",") if s]
    for i, (seed, kind) in enumerate(runs):
        t = time.perf_counter()
        r = harness.run_cell(cell, seed, args.seconds, False, t_start=t,
                             plant=PLANTS.get(kind), warm_up=i == 0)
        print(json.dumps({"workload": cell.name, "seed": seed, "run": kind,
                          "correct": r["correct"], "attempted": r["attempted"],
                          "checks": r["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
