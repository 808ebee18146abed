"""Test configuration: force an 8-device virtual CPU platform.

Tests must run without TPU hardware and must exercise multi-device
sharding, so we ask XLA for 8 host-platform devices.  This is the
multi-node-without-a-real-cluster trick of the reference test harness
(reference raftsql_test.go:16-28, loopback TCP on localhost ports) in its
TPU-native form.

CPU is asked for by name, before jax is imported — the device rule
(raftsql_tpu/utils/device.py) never falls back to it on its own.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running sweeps (deep chaos schedules), excluded "
        "from the tier-1 run via -m 'not slow'")


def free_port() -> int:
    """An OS-assigned localhost port.  Bind-and-release has the usual
    TOCTOU window: the OS may hand the released port to someone else
    before the caller binds it.  Ephemeral-range collisions are rare and
    the suites run nodes that fail loudly on bind conflict; callers that
    need a narrower window should reserve with `reserve_ports` instead."""
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def reserve_ports(n: int):
    """Bind n distinct localhost ports and HOLD them; returns
    (ports, release) where release() closes the sockets.  Guarantees
    in-batch uniqueness and shrinks the reuse window to after release."""
    import socket
    socks = []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
    ports = [s.getsockname()[1] for s in socks]

    def release():
        for s in socks:
            s.close()

    return ports, release
