"""RemoteDistributor: multi-host launch over an exec transport, proven
with 2 "hosts" on localhost (the SURVEY §4 answer to testing pod
topologies without a pod).  Covers the env contract, cross-host
control-plane rendezvous, stdout-frame integrity, typed failure
propagation with host-tagged stderr tails, timeout root-causing, and the
ssh command shape."""

import os
import sys

import pytest

from tpuframe.launch import (
    Distributor,
    RemoteDistributor,
    RemoteLaunchError,
    ssh_connect,
)

# Local-exec transport: `env` passes argv through verbatim (no shell) and
# keeps the agents CPU-only.
_LOCAL = ["env", "JAX_PLATFORMS=cpu"]


def _two_hosts(**kw):
    kw.setdefault("timeout_s", 120.0)
    return RemoteDistributor(
        ["hostA", "hostB"],
        connect=lambda host: list(_LOCAL),
        remote_python=sys.executable,
        master_addr="127.0.0.1",
        **kw,
    )


def _echo_contract():
    return {
        "rank": os.environ["RANK"],
        "local_rank": os.environ["LOCAL_RANK"],
        "world": os.environ["WORLD_SIZE"],
        "master": os.environ["MASTER_ADDR"],
        "coord": os.environ["TPUFRAME_COORDINATOR"],
    }


def _cp_allgather():
    """Rendezvous across the two agent processes through the C++ control
    plane and allgather each rank's id — real cross-"host" communication,
    not just env echoing."""
    from tpuframe.core.native import ControlPlane

    with ControlPlane() as cp:
        cp.barrier()
        mine = f"rank{cp.rank}".encode()
        return [b.decode() for b in cp.allgather_bytes(mine)]


def test_remote_env_contract_and_rank0_result():
    out = _two_hosts().run(_echo_contract)
    assert out["rank"] == "0" and out["local_rank"] == "0"
    assert out["world"] == "2" and out["master"] == "127.0.0.1"
    assert out["coord"].startswith("127.0.0.1:")


def test_remote_cross_host_control_plane():
    assert _two_hosts().run(_cp_allgather) == ["rank0", "rank1"]


def _print_then_return():
    print("progress line 1")
    print("TPUFRAME_RESULT is just text mid-line, not a frame")
    return {"answer": 42}


def test_remote_stdout_passthrough_keeps_frame_intact(capfd):
    out = _two_hosts().run(_print_then_return)
    assert out == {"answer": 42}
    # rank 0's ordinary stdout streamed through to the driver
    assert "progress line 1" in capfd.readouterr().out


def _fail_on_rank1():
    import sys as _sys

    if os.environ["RANK"] == "1":
        print("about to explode on hostB", file=_sys.stderr)
        raise ValueError("rank1 typed failure")
    return "ok"


def test_remote_typed_failure_with_host_tagged_tail():
    with pytest.raises(ValueError, match="rank1 typed failure") as exc_info:
        _two_hosts().run(_fail_on_rank1)
    cause = exc_info.value.__cause__
    assert isinstance(cause, RemoteLaunchError)
    assert cause.host == "hostB" and cause.rank == 1
    assert "about to explode on hostB" in cause.stderr_tail


def _crash_or_hang():
    import time

    if os.environ["RANK"] == "0":
        raise RuntimeError("root cause on hostA")
    time.sleep(60)


@pytest.mark.slow
def test_remote_timeout_surfaces_crashed_peer():
    with pytest.raises(RuntimeError, match="root cause on hostA"):
        _two_hosts(timeout_s=15.0).run(_crash_or_hang)


def _hang():
    import time

    time.sleep(60)


def test_remote_run_wide_timeout():
    import time

    t0 = time.monotonic()
    with pytest.raises(TimeoutError, match="still running"):
        _two_hosts(timeout_s=3.0).run(_hang)
    assert time.monotonic() - t0 < 30


def test_ssh_default_command_shape():
    rd = RemoteDistributor(["tpu-host-0"])
    cmd = rd._command("tpu-host-0")
    assert cmd[:4] == ["ssh", "-o", "BatchMode=yes", "tpu-host-0"]
    # shell transport: the agent invocation is one quoted string
    assert cmd[4] == "python3 -u -m tpuframe.launch.agent"
    assert rd.connect is ssh_connect


def test_distributor_local_mode_false_delegates():
    d = Distributor(
        local_mode=False,
        hosts=["hostA", "hostB"],
        connect=lambda host: list(_LOCAL),
        remote_kwargs={
            "remote_python": sys.executable,
            "master_addr": "127.0.0.1",
        },
        timeout_s=120.0,
    )
    out = d.run(_echo_contract)
    assert out["world"] == "2"


def _device_count():
    import jax

    return jax.device_count()


@pytest.mark.slow
def test_remote_simulate_devices():
    """Pod-topology simulation crosses the launch boundary: each agent
    resolves TPUFRAME_SIMULATE_DEVICES into a virtual CPU platform before
    the payload runs."""
    out = _two_hosts(simulate_devices=4, timeout_s=300.0).run(_device_count)
    assert out == 4


def test_agent_self_terminates_on_driver_disconnect():
    """Killing the local transport client only reaches the local process
    (ssh does not signal the remote command); stdin EOF is the agent's
    death watch — an orphaned agent must exit rather than hold the
    host's chips."""
    import json
    import subprocess
    import time

    import cloudpickle

    from tpuframe.launch.agent import ORPHANED_EXIT

    payload = cloudpickle.dumps((_hang, (), {}))
    header = (
        json.dumps({"payload_bytes": len(payload), "env": {}}).encode() + b"\n"
    )
    p = subprocess.Popen(
        [sys.executable, "-u", "-m", "tpuframe.launch.agent"],
        stdin=subprocess.PIPE,
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
        env={
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            # _hang pickles by reference to this module; no driver is
            # shipping sys.path here, so do it by hand
            "PYTHONPATH": os.pathsep.join(
                [os.path.dirname(__file__), os.environ.get("PYTHONPATH", "")]
            ),
        },
    )
    try:
        p.stdin.write(header)
        p.stdin.write(payload)
        p.stdin.flush()
        time.sleep(1.0)  # let the fn start hanging
        p.stdin.close()  # driver disconnect
        assert p.wait(timeout=20) == ORPHANED_EXIT
    finally:
        if p.poll() is None:
            p.kill()


def test_distributor_local_mode_false_requires_hosts():
    with pytest.raises(ValueError, match="hosts"):
        Distributor(local_mode=False)


def _rank1_dies_rank0_hangs():
    import signal
    import time

    if os.environ["RANK"] == "1":
        time.sleep(2.0)  # let the beacon be seen first
        os.kill(os.getpid(), signal.SIGKILL)
    time.sleep(120)


@pytest.mark.slow
def test_heartbeat_detects_worker_behind_lingering_transport(tmp_path):
    """The case process-polling can NOT see: the local transport client
    outlives the remote worker (ssh does exactly this for host-side
    kills).  The worker's beacon goes silent -> WorkerLostError within
    seconds, not after the run deadline."""
    import stat
    import time

    from tpuframe.launch import WorkerLostError

    # a "transport" that keeps living for a minute after the worker dies
    wrapper = tmp_path / "lingering_python.sh"
    wrapper.write_text(
        f"#!/bin/sh\n{sys.executable} \"$@\"\nrc=$?\nsleep 60\nexit $rc\n"
    )
    wrapper.chmod(wrapper.stat().st_mode | stat.S_IEXEC)

    rd = RemoteDistributor(
        ["hostA", "hostB"],
        connect=lambda host: list(_LOCAL),
        remote_python=str(wrapper),
        master_addr="127.0.0.1",
        heartbeat_timeout_s=3.0,
        timeout_s=300.0,
    )
    t0 = time.monotonic()
    with pytest.raises(WorkerLostError) as exc_info:
        rd.run(_rank1_dies_rank0_hangs)
    elapsed = time.monotonic() - t0
    assert exc_info.value.rank == 1
    assert elapsed < 60, f"detection took {elapsed:.1f}s"


def _rank1_raises():
    if os.environ["RANK"] == "1":
        raise ValueError("delivered failure frame")
    return "ok"


@pytest.mark.slow
def test_wedged_transport_failure_frame_surfaces(tmp_path):
    """A FAILURE frame delivered just before the transport wedges must
    surface as the typed exception promptly — not ride to TimeoutError."""
    import stat
    import time

    wrapper = tmp_path / "lingering_python.sh"
    wrapper.write_text(
        f"#!/bin/sh\n{sys.executable} \"$@\"\nsleep 60\nexit 0\n"
    )
    wrapper.chmod(wrapper.stat().st_mode | stat.S_IEXEC)
    rd = RemoteDistributor(
        ["hostA", "hostB"],
        connect=lambda host: list(_LOCAL),
        remote_python=str(wrapper),
        master_addr="127.0.0.1",
        timeout_s=300.0,
    )
    t0 = time.monotonic()
    with pytest.raises(ValueError, match="delivered failure frame"):
        rd.run(_rank1_raises)
    assert time.monotonic() - t0 < 60
