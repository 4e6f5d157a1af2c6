"""Fault injection for the fleet manager's preemption-recovery loop
(scripts/run_manager.py — the reference's run_manager.py:94-146 semantics:
poll health, and on an unhealthy TPU kill the process group, recreate the
TPU, relaunch).  The reference had no tests for this path at all; here the
TPU lifecycle is simulated with shell commands against counter files and
the sleeps are patched out, so a full preemption round-trip runs in
seconds."""
import importlib.util
import os
import types


def _load_run_manager():
    path = os.path.join(os.path.dirname(__file__), "..", "scripts",
                        "run_manager.py")
    spec = importlib.util.spec_from_file_location("run_manager_under_test",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def preemption_recovery_test(tmp_path, monkeypatch):
    rm = _load_run_manager()
    monkeypatch.setattr(rm.time, "sleep", lambda *_: None)
    monkeypatch.setattr(rm.random, "randint", lambda *_: 0)

    d = str(tmp_path)
    # health: healthy except on its 3rd invocation (0-based call index 2 —
    # the second POLL tick, after one healthy tick) -> simulated preemption
    health = (f"c=$(cat {d}/hc 2>/dev/null || echo 0); "
              f"echo $((c+1)) > {d}/hc; [ \"$c\" -ne 2 ]")
    create = f"echo created >> {d}/creates.log"
    delete = f"echo deleted >> {d}/deletes.log"
    # first launch: park; second launch (marker exists): exit 0 -> done
    run_cmd = (f"if [ -f {d}/relaunched ]; then exit 0; "
               f"else touch {d}/relaunched; exec sleep 600; fi")

    args = types.SimpleNamespace(
        run_command=run_cmd, model_path=d, create_cmd=create,
        health_cmd=health, delete_cmd=delete, poll_interval=0,
        poll_jitter=0, stall_timeout=0, max_restarts=5,
        # ``Manager.kill`` waits this long after its SIGTERM before the
        # SIGKILL: where the parked launch inherits an ignored SIGTERM, the
        # default of 600 s holds a test worker for ten minutes
        term_grace=1)
    rm.Manager(args).run()

    log = open(os.path.join(d, "run.log")).read()
    assert "restarting (#1)" in log, log
    assert "training exited rc=0; done" in log, log
    # preemption path: initial create + recreate (delete then create again)
    assert len(open(f"{d}/creates.log").read().splitlines()) == 2
    assert len(open(f"{d}/deletes.log").read().splitlines()) == 2  # recreate + final
    assert os.path.exists(f"{d}/relaunched")


def stall_watchdog_test(tmp_path, monkeypatch):
    """A run whose metrics.jsonl heartbeat goes stale counts as stalled and
    is restarted even though the TPU reports healthy (beyond the reference,
    which only watched TPU health)."""
    rm = _load_run_manager()
    # tiny REAL sleeps: a no-op sleep lets the poll loop outrun the
    # relaunched child's exit and burn through max_restarts.  rm.time is the
    # global time module — bind the ORIGINAL sleep before patching it
    real_sleep = rm.time.sleep
    monkeypatch.setattr(rm.time, "sleep",
                        lambda t=0: real_sleep(min(t, 0.2) if t else 0.2))
    monkeypatch.setattr(rm.random, "randint", lambda *_: 0)

    d = str(tmp_path)
    hb = os.path.join(d, "metrics.jsonl")
    open(hb, "w").write("{}\n")
    os.utime(hb, (0, 0))  # heartbeat frozen in 1970 -> always stale
    run_cmd = (f"if [ -f {d}/relaunched ]; then exit 0; "
               f"else touch {d}/relaunched; exec sleep 600; fi")
    args = types.SimpleNamespace(
        run_command=run_cmd, model_path=d, create_cmd="", health_cmd="",
        delete_cmd="", poll_interval=0, poll_jitter=0, stall_timeout=1,
        max_restarts=3)

    # after the relaunch, let the run count as done on its clean exit even
    # though the heartbeat file stays stale: exit-while-healthy breaks first
    rm.Manager(args).run()
    log = open(os.path.join(d, "run.log")).read()
    assert "stalled=True" in log, log
    assert "training exited rc=0; done" in log, log
