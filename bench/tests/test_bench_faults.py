"""The check that decides ``correct`` fails when the timed path is broken
underneath it: the control (every request answered a decade of tau looser)
and each fault a served cell can have, planted at the server's public
surface and driven through the rest of a run on the CPU at the
configuration's CPU test size."""
from concurrent.futures import Future
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import control, harness  # noqa: E402


def run(cell, plant=None, seed=2**31 + 29):
    return harness.run_cell(cell, seed, 2.0, False,
                            t_start=time.perf_counter(), log=lambda s: None,
                            plant=plant)


@pytest.mark.parametrize("seed", [2**31 + 29, 7])
def test_control_looser_answers_are_not_correct(on_cpu, tiny_cell, seed):
    out = run(tiny_cell("isabel.ladder"), plant=control.plant_control,
              seed=seed)
    assert not out["correct"]
    assert out["checks"]["bound_over_tau"]["value"] > 1.0
    assert out["failed"] == 0          # it claims to certify every answer


def test_stale_state_is_not_correct(on_cpu, tiny_cell):
    """Each session's later answers keep its first answer's values, while
    the planes (and so the reported bound) move on."""
    out = run(tiny_cell("isabel.ladder"), plant=control.plant_stale)
    assert not out["correct"]
    assert out["checks"]["err_over_bound"]["value"] > 1.0


def test_answer_altered_where_produced_is_not_correct(on_cpu, tiny_cell):
    """One value of each variable moved by far more than its bound."""
    def make(current, variables):
        def altered(v):
            data, bound = current(v)
            data = np.array(data, copy=True)
            i = int(np.argmax(np.abs(data)))
            data.flat[i] += np.sign(data.flat[i]) * max(100.0 * bound, 1.0)
            return data, bound
        return altered
    out = run(tiny_cell("isabel.ladder"),
              plant=lambda server: control.plant_values(server, make))
    assert not out["correct"]
    assert out["checks"]["err_over_bound"]["value"] > 1.0


def test_uncertified_or_failed_answers_are_not_correct(on_cpu, tiny_cell):
    calls = []

    def plant(server):
        submit = server.submit

        def flaky(req):
            calls.append(req)
            n = len(calls) % 5
            inner, outer = submit(req), Future()

            def relay(f):
                if n == 3:
                    outer.set_exception(RuntimeError("planted failure"))
                elif f.exception() is not None:
                    outer.set_exception(f.exception())
                else:
                    outer.set_result(dict(f.result(),
                                          guaranteed=f.result()["guaranteed"]
                                          and n != 4))
            inner.add_done_callback(relay)
            return outer
        server.submit = flaky
    out = run(tiny_cell("isabel.ladder"), plant=plant)
    assert not out["correct"]
    assert out["checks"]["unanswered"]["value"] > 0
    assert out["checks"]["uncertified"]["value"] > 0
    assert out["failed"] >= out["checks"]["unanswered"]["value"]
