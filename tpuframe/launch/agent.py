"""Per-host launch agent: ``python -m tpuframe.launch.agent``.

The remote half of :class:`tpuframe.launch.RemoteDistributor` — the piece
the reference outsources to Spark executors / Ray actors (worker placement,
`/root/reference/01_torch_distributor/01_basic_torch_distributor.py:360-367`,
`/root/reference/05_ray/01_fashion_mnist_pytorch_ray.ipynb:cell-5`).  One
agent runs per host and executes the shipped train fn as that host's rank.

Protocol (transport-agnostic: anything that can exec a command and pipe
stdio works — ssh, kubectl exec, docker exec, or a bare subprocess):

- **stdin**: one JSON header line ``{"payload_bytes": N, "env": {...}}``
  followed by exactly ``N`` bytes of cloudpickled ``(fn, args, kwargs)``.
- **stdout**: the fn's own stdout passes through untouched; the agent's
  last line is ``TPUFRAME_RESULT <base64(cloudpickle(outcome))>`` where
  ``outcome`` is ``{"ok": True, "value": ...}`` or
  ``{"ok": False, "error": exc}``.
- **stderr**: passes through (the driver keeps a per-rank tail).
- **exit code**: 0 on success, nonzero on failure — the result frame still
  carries the typed exception when it was picklable, so restart policies
  can dispatch on the type.

The env contract (``RANK``/``WORLD_SIZE``/``MASTER_ADDR``/…) arrives in
the header and is applied to ``os.environ`` *before* the payload is
unpickled; the header's ``PYTHONPATH`` additionally lands on ``sys.path``
so by-reference functions resolve.  Vars that must exist before
interpreter start belong in the *transport command* (the ``connect``
hook), not the header — by header time the interpreter is already up.
"""

from __future__ import annotations

import base64
import json
import os
import pickle
import sys
import threading

RESULT_SENTINEL = "TPUFRAME_RESULT "

#: Exit code of the stdin-EOF watchdog (driver/transport gone).
ORPHANED_EXIT = 17


def _arm_orphan_watchdog() -> None:
    """Self-terminate when the driver disappears.

    The driver holds our stdin open for the whole run.  Killing the local
    transport client (ssh) does NOT signal a non-pty remote command — an
    orphaned agent would keep training and hold the host's chips.  EOF on
    stdin is the one signal every stdio transport delivers on disconnect,
    so a blocked read doubles as a zero-cost death watch.
    """

    def watch() -> None:
        try:
            # raw-fd read, NOT sys.stdin.buffer: a daemon thread blocked
            # inside the buffered reader holds its lock and aborts
            # interpreter shutdown ("could not acquire lock ... at
            # interpreter shutdown")
            fd = sys.stdin.fileno()
            while os.read(fd, 4096):
                pass  # stray bytes after the payload: ignore, keep watching
        except Exception:
            pass
        os._exit(ORPHANED_EXIT)

    threading.Thread(target=watch, daemon=True, name="orphan-watchdog").start()


def _emit(outcome: dict) -> None:
    import cloudpickle

    try:
        blob = cloudpickle.dumps(outcome)
    except Exception as e:  # unpicklable return value
        blob = pickle.dumps(
            {"ok": False, "error": RuntimeError(f"result not picklable: {e}")}
        )
    # leading newline guards against the fn leaving a partial stdout line
    sys.stdout.write("\n" + RESULT_SENTINEL + base64.b64encode(blob).decode() + "\n")
    sys.stdout.flush()


def main() -> None:
    header = json.loads(sys.stdin.buffer.readline())
    env = dict(header.get("env", {}))
    os.environ.update(env)
    if env.get("PYTHONPATH"):
        for p in reversed(env["PYTHONPATH"].split(os.pathsep)):
            if p and p not in sys.path:
                sys.path.insert(0, p)

    n = int(header["payload_bytes"])
    blob = sys.stdin.buffer.read(n)
    if len(blob) != n:
        _emit(
            {
                "ok": False,
                "error": RuntimeError(
                    f"truncated payload: got {len(blob)}/{n} bytes"
                ),
            }
        )
        raise SystemExit(1)
    _arm_orphan_watchdog()

    # preemption watcher: a SIGTERM to this host's agent (spot reclaim,
    # maintenance drain, `kubectl delete pod` grace period) sets the
    # cross-thread flag the Trainer converts into a last-chance
    # checkpoint + Preempted exit.  TPUFRAME_PREEMPT_SIGNALS=0 opts out.
    if os.environ.get("TPUFRAME_PREEMPT_SIGNALS", "1") != "0":
        from tpuframe.fault import preempt

        preempt.install()

    if env.get("TPUFRAME_HB_PORT"):
        from tpuframe.core.native import maybe_start_beacon

        maybe_start_beacon()

    if env.get("TPUFRAME_SIMULATE_DEVICES"):
        # virtual CPU mesh for pod-topology tests; must beat any real
        # backend init (simulate_cpu_devices sets env + live jax config)
        from tpuframe.core.runtime import simulate_cpu_devices

        simulate_cpu_devices(int(env["TPUFRAME_SIMULATE_DEVICES"]))

    import cloudpickle

    fn, args, kwargs = cloudpickle.loads(blob)
    try:
        value = fn(*args, **kwargs)
    except BaseException as e:  # recorded in the frame, then re-raised
        try:
            cloudpickle.dumps(e)
            _emit({"ok": False, "error": e})
        except Exception:
            _emit({"ok": False, "error": RuntimeError(repr(e))})
        # distinguishable exit code (143): the driver's restart policy
        # can classify a preempted host without unpickling the frame
        from tpuframe.fault.preempt import reraise_for_exit

        reraise_for_exit(e)
    _emit({"ok": True, "value": value})


if __name__ == "__main__":
    main()
