"""RemoteDistributor: place rank-k workers on N hosts over an exec transport.

The reference's launchers get worker *placement* for free from a resident
cluster runtime — Spark executors
(`/root/reference/01_torch_distributor/01_basic_torch_distributor.py:360-367`)
or Ray actors (`/root/reference/05_ray/01_fashion_mnist_pytorch_ray.ipynb:
cell-5`).  A TPU pod has no resident runtime: you reach hosts by exec —
ssh, ``kubectl exec``, ``gcloud compute tpus tpu-vm ssh --worker=all``.
This driver owns that path:

- one :mod:`tpuframe.launch.agent` per host, started through a pluggable
  ``connect`` hook (argv prefix; default ssh with BatchMode),
- the torchrun-style env contract (``MASTER_ADDR``/``RANK``/``WORLD_SIZE``
  + ``TPUFRAME_*``) shipped in the agent's stdin header,
- the train fn cloudpickled over stdin (no shared filesystem needed),
- per-rank stderr tails streamed back and attached to failures,
- rank 0's picklable result aggregated back to the caller — the same
  ``.run()`` surface as the local :class:`~tpuframe.launch.Distributor`.

Failure semantics mirror the local Distributor: a worker's own typed
exception re-raises on the driver with a :class:`RemoteLaunchError`
(host + rank + exit code + stderr tail) as ``__cause__``; a run-wide
deadline caps the whole launch, and once one rank has failed its hung
peers get a short grace, not the rest of the deadline.
"""

from __future__ import annotations

import base64
import os
import pickle
import secrets
import shlex
import subprocess
import sys
import threading
import time
from collections import deque
from typing import Any, Callable, Mapping, Sequence

import cloudpickle

from tpuframe.launch.agent import ORPHANED_EXIT, RESULT_SENTINEL
from tpuframe.launch.distributor import (
    _KILL_CODES,
    _STDERR_TAIL,
    DistributorError,
    _free_port,
    _stale_rank_check,
    await_and_root_cause,
)


class RemoteLaunchError(DistributorError):
    """A remote worker exited nonzero (or vanished) without a recoverable
    typed exception; carries host, rank, exit code, and stderr tail."""

    def __init__(self, host: str, rank: int, returncode: int, stderr_tail: str):
        self.host = host
        # skip DistributorError.__init__ to control the message
        RuntimeError.__init__(
            self,
            f"worker rank {rank} on host {host!r} exited with code "
            f"{returncode}\n--- stderr tail ---\n{stderr_tail}",
        )
        self.rank = rank
        self.returncode = returncode
        self.stderr_tail = stderr_tail


def ssh_connect(host: str) -> list[str]:
    """Default transport: non-interactive ssh (fails instead of prompting)."""
    return ["ssh", "-o", "BatchMode=yes", host]


#: Per-rank launch-contract and host-local infrastructure env — values the
#: launcher COMPUTES for each worker (rank, coordinator address, control-
#: plane ports/credentials, host scratch) rather than copying from the
#: client env, so they are deliberately NOT part of :func:`all_env_vars`.
#: Declared so every ``TPUFRAME_*`` read in the tree is accounted for in
#: exactly one registry (``tpuframe.lint`` rule KN001); table in FAULT.md.
LAUNCH_CONTRACT_ENV_VARS = (  # tpuframe-lint: not-shipped
    "TPUFRAME_PROCESS_ID",
    "TPUFRAME_NUM_PROCESSES",
    "TPUFRAME_COORDINATOR",
    "TPUFRAME_CP_PORT",
    "TPUFRAME_CP_TOKEN",
    "TPUFRAME_CP_BIND",
    "TPUFRAME_HB_PORT",
    "TPUFRAME_HB_ADDR",
    "TPUFRAME_SIMULATE_DEVICES",
    "TPUFRAME_RESULT_DIR",
    "TPUFRAME_LOCAL_SCRATCH",
    "TPUFRAME_NATIVE_KEEP_BUILDS",
)

#: value domains for the launch contract (KN007).  All "restart" by
#: construction — these are per-worker identity/infrastructure values
#: the launcher computes at spawn; rewriting them inside a live worker
#: is meaningless.
LAUNCH_CONTRACT_ENV_DOMAINS = {
    "TPUFRAME_PROCESS_ID": {
        "type": "int", "range": (0, None), "apply": "restart"},
    "TPUFRAME_NUM_PROCESSES": {
        "type": "int", "range": (1, None), "apply": "restart"},
    "TPUFRAME_COORDINATOR": {"type": "str", "apply": "restart"},
    "TPUFRAME_CP_PORT": {
        "type": "int", "range": (1, 65535), "apply": "restart"},
    "TPUFRAME_CP_TOKEN": {"type": "str", "apply": "restart"},
    "TPUFRAME_CP_BIND": {"type": "str", "apply": "restart"},
    "TPUFRAME_HB_PORT": {
        "type": "int", "range": (1, 65535), "apply": "restart"},
    "TPUFRAME_HB_ADDR": {"type": "str", "apply": "restart"},
    "TPUFRAME_SIMULATE_DEVICES": {
        "type": "int", "range": (0, None), "apply": "restart"},
    "TPUFRAME_RESULT_DIR": {"type": "path", "apply": "restart"},
    "TPUFRAME_LOCAL_SCRATCH": {"type": "path", "apply": "restart"},
    "TPUFRAME_NATIVE_KEEP_BUILDS": {
        "type": "int", "range": (0, None), "apply": "restart"},
}


def all_env_vars() -> tuple[str, ...]:
    """Every spine's env-knob list, aggregated — THE single registry
    consumed by remote worker shipping (below) and the doctor.

    Each spine declares its own list next to its knobs
    (``OBSERVABILITY_ENV_VARS``, ``COMPILE_ENV_VARS``,
    ``HEALTH_ENV_VARS``, ``SERVE_ENV_VARS``, ``PERF_ENV_VARS``,
    ``COMMS_ENV_VARS``, ``AUTOTUNE_ENV_VARS``, ``PROFILE_ENV_VARS``,
    ``MEMORY_ENV_VARS``);
    new spines add
    themselves HERE, and both consumers pick them up for free — the
    concrete first step toward the ROADMAP item-5 typed knob registry.
    All nine source modules are
    stdlib-only imports (no jax), so this resolves on a wedged-backend
    doctor run too.  The invariant linter (``tpuframe.lint`` rule
    KN004) fails tier-1 if a knob list exists that this aggregate does
    not reach.
    """
    from tpuframe.autotune.config import AUTOTUNE_ENV_VARS
    from tpuframe.compile.cache import COMPILE_ENV_VARS
    from tpuframe.core.workspace import PERF_ENV_VARS
    from tpuframe.fault.health import HEALTH_ENV_VARS
    from tpuframe.parallel.comms_env import COMMS_ENV_VARS
    from tpuframe.serve.admission import SERVE_ENV_VARS
    from tpuframe.track.device_time import PROFILE_ENV_VARS
    from tpuframe.track.memory import MEMORY_ENV_VARS
    from tpuframe.track.telemetry import OBSERVABILITY_ENV_VARS

    return (OBSERVABILITY_ENV_VARS + COMPILE_ENV_VARS + HEALTH_ENV_VARS
            + SERVE_ENV_VARS + PERF_ENV_VARS + COMMS_ENV_VARS
            + AUTOTUNE_ENV_VARS + PROFILE_ENV_VARS + MEMORY_ENV_VARS)


class _Worker:
    """One spawned agent: process handle + stdio pump threads + outcome."""

    def __init__(self, rank: int, host: str, proc: subprocess.Popen,
                 payload: bytes, header: bytes, echo_stdout: bool):
        self.rank = rank
        self.host = host
        self.proc = proc
        self.outcome: dict | None = None
        self.frame_error: Exception | None = None
        self.stderr_tail: deque[bytes] = deque(maxlen=200)
        self._threads = [
            threading.Thread(
                target=self._pump_stdin, args=(header, payload), daemon=True
            ),
            threading.Thread(target=self._pump_stdout, args=(echo_stdout,),
                             daemon=True),
            threading.Thread(target=self._pump_stderr, daemon=True),
        ]
        for t in self._threads:
            t.start()

    def _pump_stdin(self, header: bytes, payload: bytes) -> None:
        try:
            self.proc.stdin.write(header)
            self.proc.stdin.write(payload)
            self.proc.stdin.flush()
            # stdin stays OPEN: it is the agent's death watch — EOF means
            # "driver gone, self-terminate" (agent._arm_orphan_watchdog),
            # the one disconnect signal every stdio transport delivers
        except (BrokenPipeError, OSError):
            pass  # agent died before reading; its exit code tells the story

    def close_stdin(self) -> None:
        try:
            self.proc.stdin.close()
        except Exception:
            pass

    def _pump_stdout(self, echo: bool) -> None:
        sentinel = RESULT_SENTINEL.encode()
        for line in self.proc.stdout:
            if line.startswith(sentinel):
                try:
                    self.outcome = pickle.loads(
                        base64.b64decode(line[len(sentinel):].strip())
                    )
                except Exception as e:  # torn frame (killed mid-write)
                    self.frame_error = e
            elif echo:
                sys.stdout.write(
                    f"[{self.host}:{self.rank}] {line.decode(errors='replace')}"
                )
        self.proc.stdout.close()

    def _pump_stderr(self) -> None:
        for line in self.proc.stderr:
            self.stderr_tail.append(line)
        self.proc.stderr.close()

    def tail(self) -> str:
        return b"".join(self.stderr_tail)[-_STDERR_TAIL:].decode(errors="replace")

    def join_pumps(self, timeout: float = 5.0) -> None:
        for t in self._threads:
            t.join(timeout)


class RemoteDistributor:
    """``.run(fn, *args, **kwargs)`` across N hosts; returns rank 0's result.

    Args:
      hosts: one entry per host (= per rank; TPU pods run one process per
        host driving all local chips).  Entries are whatever ``connect``
        understands — DNS names for ssh, pod names for ``kubectl exec``.
      connect: ``host -> argv prefix`` hook (default: ssh BatchMode).
        Return ``[]`` to exec locally — the 2-"hosts"-on-localhost test
        mode, and the escape hatch for custom launch fabrics.
      remote_python: python executable on the hosts (default ``python3``).
      master_addr: coordinator address *as reachable from the hosts*
        (default ``hosts[0]``); becomes ``MASTER_ADDR`` and the control
        plane's hub address.
      master_port / cp_port: rendezvous ports (0 = pick free ones — only
        correct when the driver shares the network namespace with the
        hosts, i.e. localhost testing; real pods should pass fixed ports).
      env: extra env vars shipped to every worker (credentials etc.,
        the reference's ``DATABRICKS_HOST/TOKEN`` pattern,
        `/root/reference/setup/00_setup.py:86-92`).
      ship_pythonpath: also ship the driver's ``sys.path`` as PYTHONPATH —
        right for localhost/shared-filesystem clusters, wrong for
        heterogeneous installs (default: only for non-shell transports,
        which are typically local exec or same-image containers).
      shell_quote: the transport re-parses the command through a remote
        shell (ssh does; argv-passthrough transports like ``env`` /
        ``kubectl exec …​ --`` / ``docker exec`` do not).  Default: only
        for the built-in ssh transport.
      stream_output: echo every worker's stdout/stderr lines to the driver,
        prefixed ``[host:rank]`` (rank 0's stdout always streams).
      timeout_s: run-wide wall-clock cap.
    """

    def __init__(
        self,
        hosts: Sequence[str],
        *,
        connect: Callable[[str], list[str]] | None = None,
        remote_python: str = "python3",
        master_addr: str | None = None,
        master_port: int = 0,
        cp_port: int = 0,
        env: Mapping[str, str] | None = None,
        ship_pythonpath: bool | None = None,
        shell_quote: bool | None = None,
        simulate_devices: int | None = None,
        stream_output: bool = False,
        timeout_s: float = 600.0,
        heartbeat_timeout_s: float | None = 15.0,
        driver_addr: str | None = None,
        hb_port: int = 0,
    ):
        if not hosts:
            raise ValueError("hosts must be non-empty")
        self.hosts = list(hosts)
        self.connect = connect or ssh_connect
        self.shell_quote = (
            self.connect is ssh_connect if shell_quote is None else shell_quote
        )
        self.remote_python = remote_python
        self.master_addr = master_addr
        self.master_port = master_port
        self.cp_port = cp_port
        self.extra_env = dict(env or {})
        self.ship_pythonpath = ship_pythonpath
        self.simulate_devices = simulate_devices
        self.stream_output = stream_output
        self.timeout_s = timeout_s
        self.heartbeat_timeout_s = heartbeat_timeout_s
        # beacons target the DRIVER (which may be neither of the hosts);
        # default works for localhost testing — real pods pass the
        # driver's host-reachable address + a fixed, unfirewalled hb_port
        self.driver_addr = driver_addr
        self.hb_port = hb_port

    # -- env -----------------------------------------------------------------
    def _worker_env(self, rank: int, master: str, port: int, cp_port: int,
                    token: str, hb_port: int | None) -> dict[str, str]:
        world = len(self.hosts)
        env = dict(self.extra_env)
        # Driver-side observability/fault knobs ship to every host by
        # default (explicit ``env=`` entries win).  The local Distributor
        # inherits the whole driver environ; remote hosts start from the
        # stdin header alone, and a fleet whose ranks silently ran
        # without telemetry cannot be skew-analyzed after the fact
        # (``python -m tpuframe.track analyze`` needs every rank's log).
        # compile-cache knobs ride along for the same reason: a worker
        # restarted on the same host (or a new rank joining it) must hit
        # the warm cache the driver configured, not recompile cold.
        # Health-sentinel knobs too: divergence thresholds and rollback
        # perturbation must be fleet-uniform, or ranks disagree on
        # whether a step was bad and the synchronous loop deadlocks on
        # one rank raising Divergence alone.  Serve knobs likewise: a
        # serving fleet whose replicas disagree on SLO/shed policy
        # load-balances incoherently.  all_env_vars() is the one list.
        for var in all_env_vars():
            if var in os.environ and var not in env:
                env[var] = os.environ[var]
        env.update(
            MASTER_ADDR=master,
            MASTER_PORT=str(port),
            RANK=str(rank),
            LOCAL_RANK="0",  # one process per host owns all local chips
            WORLD_SIZE=str(world),
            TPUFRAME_NUM_PROCESSES=str(world),
            TPUFRAME_PROCESS_ID=str(rank),
        )
        if world > 1:
            env["TPUFRAME_COORDINATOR"] = f"{master}:{port}"
            env["TPUFRAME_CP_PORT"] = str(cp_port)
            # plain assignment: monitor/hub were built with this token
            env["TPUFRAME_CP_TOKEN"] = token
        if self.simulate_devices:
            # the agent resolves this into a virtual CPU platform before
            # the payload runs (env + live jax config)
            env["TPUFRAME_SIMULATE_DEVICES"] = str(self.simulate_devices)
        if hb_port:
            env["TPUFRAME_HB_PORT"] = str(hb_port)
            env["TPUFRAME_HB_ADDR"] = self.driver_addr or master
        ship = self.ship_pythonpath
        if ship is None:
            ship = not self.shell_quote
        if ship:
            path = [p for p in sys.path if p and os.path.isdir(p)]
            env["PYTHONPATH"] = os.pathsep.join(path)
        return env

    _free_port = staticmethod(_free_port)

    def _command(self, host: str) -> list[str]:
        prefix = list(self.connect(host))
        agent = [self.remote_python, "-u", "-m", "tpuframe.launch.agent"]
        if self.shell_quote:
            # ssh-like transports re-parse the command through the remote
            # shell; quote so argv survives the round-trip
            return prefix + [" ".join(shlex.quote(a) for a in agent)]
        return prefix + agent

    # -- run -----------------------------------------------------------------
    def run(self, fn: Callable, *args: Any, **kwargs: Any) -> Any:
        """Execute ``fn(*args, **kwargs)`` as rank k on ``hosts[k]``;
        return rank 0's picklable result."""
        import json

        world = len(self.hosts)
        master = self.master_addr or self.hosts[0]
        port = self.master_port or self._free_port()
        cp_port = self.cp_port or self._free_port()
        # unguessable run-scoped control-plane token: the hub is reachable
        # on the pod network, and the token ships out-of-band (stdin
        # header), so strangers who can reach the port still can't join
        token = self.extra_env.get("TPUFRAME_CP_TOKEN") or secrets.token_hex(16)
        payload = cloudpickle.dumps((fn, args, kwargs))

        monitor = None
        hb_port: int | None = None
        if self.heartbeat_timeout_s and world > 1:
            try:
                from tpuframe.core.native import HeartbeatMonitor

                hb_port = self.hb_port or self._free_port()
                monitor = HeartbeatMonitor(hb_port, world, token=token)
            except Exception:
                monitor, hb_port = None, None  # liveness is best-effort

        workers: list[_Worker] = []
        deadline = time.monotonic() + self.timeout_s
        try:
            for rank, host in enumerate(self.hosts):
                header = (
                    json.dumps(
                        {
                            "payload_bytes": len(payload),
                            "env": self._worker_env(
                                rank, master, port, cp_port, token, hb_port
                            ),
                        }
                    ).encode()
                    + b"\n"
                )
                proc = subprocess.Popen(
                    self._command(host),
                    stdin=subprocess.PIPE,
                    stdout=subprocess.PIPE,
                    stderr=subprocess.PIPE,
                )
                workers.append(
                    _Worker(
                        rank,
                        host,
                        proc,
                        payload,
                        header,
                        echo_stdout=self.stream_output or rank == 0,
                    )
                )

            def make_failure(rank: int, code: int, w: _Worker) -> BaseException:
                w.join_pumps()
                return self._worker_failure(w, code)

            await_and_root_cause(
                [(w.rank, w.proc, w) for w in workers],
                deadline=deadline,
                timeout_s=self.timeout_s,
                make_failure=make_failure,
                kill_all=lambda: self._kill_and_reap(workers),
                describe_timeout=lambda rank: (
                    f"run exceeded {self.timeout_s}s (worker rank {rank} "
                    f"on {self.hosts[rank]!r} still running)"
                ),
                # cleanup closes stdin first, so a hung agent may exit via
                # its orphan watchdog before our kill lands — that's
                # self-inflicted, not a root cause
                self_inflicted=(*_KILL_CODES, ORPHANED_EXIT),
                health_check=self._drained_aware_check(monitor, workers),
                # every pending rank's result frame (success OR failure)
                # already in hand means only transports linger; don't let
                # them ride the run to timeout — the outcome scan below
                # raises any delivered failure
                finished_check=lambda pending: all(
                    workers[r].outcome is not None for r in pending
                ),
            )
            # a failure frame delivered by a worker whose transport wedged
            # never produced a nonzero exit for make_failure to see — scan
            # for it so the real exception surfaces, not a timeout
            for w in workers:
                if w.outcome is not None and not w.outcome.get("ok", True):
                    raise self._worker_failure(w, w.proc.returncode or 0)
        finally:
            self._kill_and_reap(workers)
            for w in workers:
                w.join_pumps()
            if monitor is not None:
                monitor.close()

        w0 = workers[0]
        if w0.outcome is None and w0.frame_error is None:
            # a big result frame (base64 of hundreds of MB) can still be
            # draining through the pump after process exit — give it real
            # time before declaring the frame missing
            w0.join_pumps(timeout=60.0)
        if w0.outcome is None:
            raise RemoteLaunchError(
                w0.host,
                0,
                w0.proc.returncode or 0,
                (f"no result frame on stdout "
                 f"(frame error: {w0.frame_error})\n" if w0.frame_error else
                 "no result frame on stdout\n") + w0.tail(),
            )
        if w0.outcome["ok"]:
            return w0.outcome["value"]
        raise w0.outcome["error"]

    def _drained_aware_check(self, monitor, workers: Sequence[_Worker]):
        """Heartbeat check that ignores ranks whose result frame already
        arrived: a cleanly-finished agent's beacon goes silent while the
        transport (ssh) may keep draining a large frame for a while — that
        rank has succeeded, not vanished."""
        base = _stale_rank_check(monitor, self.heartbeat_timeout_s)
        if base is None:
            return None

        def check(pending_ranks):
            return base({r for r in pending_ranks if workers[r].outcome is None})

        return check

    @staticmethod
    def _kill_and_reap(workers: Sequence[_Worker]) -> None:
        # Close stdin FIRST: for transports where kill() only reaches the
        # local client (ssh), the EOF is what tells the remote agent to
        # self-terminate instead of holding the host's chips.
        for w in workers:
            w.close_stdin()
        for w in workers:
            if w.proc.poll() is None:
                w.proc.kill()
        for w in workers:
            try:
                w.proc.wait(timeout=10)
            except Exception:
                pass

    @staticmethod
    def _worker_failure(w: _Worker, code: int) -> BaseException:
        launch_err = RemoteLaunchError(w.host, w.rank, code, w.tail())
        outcome = w.outcome
        if outcome is not None and not outcome.get("ok", True):
            err = outcome.get("error")
            if isinstance(err, BaseException):
                err.__cause__ = launch_err
                return err
        return launch_err
