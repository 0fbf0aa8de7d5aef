"""DistributedJobSupervisor mechanics, isolated from jax.

The supervisor never initializes a jax backend (it only spawns/monitors
worker processes), so its restart policy, health channels, and flag plumbing are
testable with trivial stand-in workers — each a tiny ``python -c`` script
injected via ``worker_cmd``. The full-stack recovery paths (real jax
workers, checkpoints, source replay) live in test_supervised_recovery.py.

Reference counterpart: Flink's JobManager restart handling —
``RestartStrategies.fixedDelayRestart(attempts, delay)`` (Job.scala:14)
plus TaskManager heartbeat-loss detection.
"""

import os
import sys
import time

import pytest

from omldm_tpu.runtime.supervisor import (
    DistributedJobSupervisor,
    FleetFailure,
    supervise_from_flags,
)

# worker that logs its argv, then exits 1 on the first incarnation (state
# file absent) and 0 on the second — the transient failure a fixed-delay
# restart is for
FLAKY = """
import os, sys
args = dict(zip(sys.argv[1::2], sys.argv[2::2]))
with open(args["--argvLog"], "a") as f:
    f.write(" ".join(sys.argv[1:]) + "\\n")
marker = args["--marker"]
if os.path.exists(marker):
    sys.exit(0)
open(marker, "w").close()
sys.exit(1)
"""

# worker that beats once, then wedges (a process stuck in a collective
# whose peer died: alive, silent, never exits)
WEDGED = """
import os, sys, time
args = dict(zip(sys.argv[1::2], sys.argv[2::2]))
d = args["--heartbeatDir"]
os.makedirs(d, exist_ok=True)
with open(os.path.join(d, "proc%s.hb" % args["--processId"]), "w") as f:
    f.write("beat")
time.sleep(300)
"""


def _supervisor(tmp_path, script, nproc=1, extra_args=(), **kw):
    return DistributedJobSupervisor(
        list(extra_args),
        nproc,
        worker_cmd=[sys.executable, "-c", script],
        run_dir=str(tmp_path / "run"),
        **kw,
    )


def test_flaky_worker_restarts_and_succeeds(tmp_path):
    argv_log = tmp_path / "argv.log"
    sup = _supervisor(
        tmp_path, FLAKY, max_restarts=1,
        extra_args=["--marker", str(tmp_path / "marker"),
                    "--argvLog", str(argv_log)],
    )
    assert sup.run() == 0
    [rec] = sup.failures
    assert rec.attempt == 1
    assert "exited 1" in rec.cause
    assert rec.failed == [0]
    assert not rec.restored  # no --checkpointDir in worker_args
    first, second = argv_log.read_text().strip().splitlines()
    # the relaunch — and only the relaunch — carries --restore true
    assert "--restore true" not in first
    assert "--restore true" in second


def test_restart_budget_exhausts_with_incident_log(tmp_path):
    sup = _supervisor(
        tmp_path, "import sys; sys.exit(7)", max_restarts=2,
        extra_args=["--x", "y"],
    )
    with pytest.raises(FleetFailure) as exc_info:
        sup.run()
    assert exc_info.value.returncode == 7
    # every attempt (initial + 2 restarts) is an incident
    assert [r.attempt for r in sup.failures] == [1, 2, 3]
    assert all("exited 7" in r.cause for r in sup.failures)


def test_heartbeat_timeout_detects_wedged_worker(tmp_path):
    sup = _supervisor(
        tmp_path, WEDGED, max_restarts=0, heartbeat_timeout_s=0.4,
    )
    start = time.monotonic()
    with pytest.raises(FleetFailure) as exc_info:
        sup.run()
    # detected by staleness, well before the worker's 300s sleep ends,
    # and the wedged process was killed on the way out
    assert time.monotonic() - start < 30
    assert "heartbeat timeout" in exc_info.value.cause
    assert exc_info.value.failed == [0]


def test_never_beating_worker_times_out_from_spawn_clock(tmp_path):
    # no beat file ever appears: the timeout clock runs from spawn
    sup = _supervisor(
        tmp_path, "import time; time.sleep(300)",
        max_restarts=0, heartbeat_timeout_s=0.4,
    )
    start = time.monotonic()
    with pytest.raises(FleetFailure, match="heartbeat timeout"):
        sup.run()
    assert time.monotonic() - start < 30


def test_one_bad_worker_fails_whole_fleet(tmp_path):
    # Flink's global restart: any lost TaskManager restarts the job, so a
    # healthy peer must be torn down with the failed one
    script = """
import sys, time
args = dict(zip(sys.argv[1::2], sys.argv[2::2]))
sys.exit(3) if args["--processId"] == "1" else time.sleep(300)
"""
    sup = _supervisor(tmp_path, script, nproc=2, max_restarts=0)
    start = time.monotonic()
    with pytest.raises(FleetFailure) as exc_info:
        sup.run()
    assert time.monotonic() - start < 30  # peer was killed, not awaited
    assert exc_info.value.failed == [1]


def test_supervise_from_flags_passthrough_and_exit_code(tmp_path):
    # the CLI adapter: supervisor-only flags are consumed, everything else
    # reaches the worker; exhausted restarts surface the worker's code
    rc = supervise_from_flags({
        "supervise": "true",
        "processes": "1",
        "restartAttempts": "1",
        "restartDelayMs": "0",
        "supervisorDir": str(tmp_path / "run"),
        "workerBoot": (
            "import sys; "
            "assert '--restartAttempts' not in sys.argv; "
            "assert '--processes' in sys.argv; "
            "sys.exit(5)"
        ),
    })
    assert rc == 5


def test_launcher_refuses_workers_sharing_a_tpu_host(monkeypatch):
    # one process per host's chips: N local workers on a TPU host would
    # each claim every chip, so the launcher refuses instead of hanging
    from jax._src import hardware_utils

    from omldm_tpu.runtime.supervisor import refuse_shared_tpu_host

    monkeypatch.setattr(
        hardware_utils, "num_available_tpu_chips_and_device_id",
        lambda: (4, hardware_utils.TpuVersion.v5e),
    )
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit, match="has TPU chips"):
        refuse_shared_tpu_host(2)
    refuse_shared_tpu_host(1)  # one process over all chips is the shape
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    refuse_shared_tpu_host(2)  # CPU workers share nothing
    # through the CLI adapter, before any worker is spawned
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(SystemExit, match="one process over all chips"):
        supervise_from_flags({"supervise": "true", "processes": "2"})
