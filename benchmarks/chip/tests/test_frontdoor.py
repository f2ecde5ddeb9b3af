"""The HTTP entry drives `POST /v1/solve:sync` end to end at a size a CPU
holds: the load generator runs as a child process, every request due in
the window is answered, and the answers decide `correct` like the
in-process entry's."""
import copy
from types import SimpleNamespace

import bench
import run
from test_correctness import FAKE_TPU, PEAK, small_config, untrained_policy


def test_the_open_loop_answers_every_request(tmp_path):
    import jax
    jax.config.update("jax_enable_x64", True)
    from repro.precision import JnpBackend
    untrained_policy(tmp_path)
    entry = bench.module("entries", "frontdoor")

    def build(cell, seed):
        return entry.build(cell, seed,
                           backend=JnpBackend(carrier_dtype="float32"))

    def entry_run(cell, seed, seconds, trace_dir=None):
        cell["config_file"] = copy.deepcopy(cell["config_file"])
        cell["config_file"].update(small_config(cell["config"]))
        cell["config_file"]["policy"] = str(tmp_path)
        cell["traffic_file"] = {"entry": "frontdoor", "loop": "open",
                                "rate": 4.0, "pool": 6, "connections": 4}
        return entry.run(cell, seed, seconds, build_fn=build)

    args = SimpleNamespace(workload="dense_gmres.inproc.closed32",
                           seed=2 ** 31 + 7, seconds=2.0, trace=0)
    out = run.run(args, FAKE_TPU, PEAK, entry_run=entry_run)
    rec = out["rec"]
    ok, numbers = run.decide(out)
    assert rec["answers"] and rec["unanswered"] == 0
    assert all(a["code"] == 200 for a in rec["answers"])
    assert all(a["t_done"] >= a["t_sent"] >= a["t_submit"] - 1e-3
               for a in rec["answers"])
    assert ok, numbers
