"""Cluster tier tests: wire format round-trip, balancer math, and a real
localhost cluster (2 server nodes + mainframe) computing correctly."""

import numpy as np
import pytest

import cekirdekler_tpu as ct
from cekirdekler_tpu.arrays.clarray import ClArray
from cekirdekler_tpu.cluster import (
    ClusterAccelerator,
    ClusterLoadBalancer,
    Command,
    CruncherClient,
    CruncherServer,
    Message,
)
from cekirdekler_tpu.cluster.netbuffer import ArrayRecord

SRC = """
__kernel void saxpy(__global float* x, __global float* y, float a) {
    int i = get_global_id(0);
    y[i] = y[i] + a * x[i];
}
"""


def _cpus(n):
    return ct.all_devices().cpus().subset(n)


# -- wire format -------------------------------------------------------------

def test_message_roundtrip():
    data = np.arange(10, dtype=np.float32)
    msg = Message(
        Command.COMPUTE,
        meta={"compute_id": 7, "global_range": 1024},
        strings=["saxpy", "k2"],
        values=[3, 2.5],
        arrays=[ArrayRecord(42, data, flags=5, epw=2, offset=4)],
    )
    decoded = Message.decode(msg.command, msg.encode())
    assert decoded.meta == msg.meta
    assert decoded.strings == ["saxpy", "k2"]
    assert decoded.values == [3, 2.5]
    rec = decoded.arrays[0]
    assert (rec.array_id, rec.flags, rec.epw, rec.offset) == (42, 5, 2, 4)
    np.testing.assert_array_equal(rec.data, data)


# -- cluster balancer --------------------------------------------------------

def test_cluster_balancer_equal_split_lcm_units():
    bal = ClusterLoadBalancer(steps=[256, 512])
    ranges, rem = bal.equal_split(4096)
    assert sum(ranges) + rem == 4096
    assert all(r % 512 == 0 for r in ranges)  # LCM(256,512)=512 chunks


def test_cluster_balancer_rebalance_moves_toward_fast_node():
    bal = ClusterLoadBalancer(steps=[64, 64])
    ranges, rem = bal.equal_split(2048)
    start = list(ranges)
    # node 0 is 4x faster
    for _ in range(8):
        ranges, rem = bal.rebalance(ranges, [10.0, 40.0], 2048)
    assert ranges[0] > start[0]
    assert ranges[0] % 64 == 0 and ranges[1] % 64 == 0
    assert sum(ranges) + rem == 2048


# -- live localhost cluster --------------------------------------------------

@pytest.fixture()
def two_servers():
    s1 = CruncherServer(devices=_cpus(2))
    s2 = CruncherServer(devices=_cpus(2))
    yield s1, s2
    s1.stop()
    s2.stop()


def test_client_setup_control_numdevices(two_servers):
    s1, _ = two_servers
    c = CruncherClient(s1.host, s1.port)
    assert c.setup(SRC) == 2
    assert c.control()
    assert c.num_devices() == 2
    c.close()


def test_cluster_compute_matches_host(two_servers):
    s1, s2 = two_servers
    n = 4096
    x = ClArray(np.arange(n, dtype=np.float32), partial_read=True, read_only=True)
    y = ClArray(np.ones(n, np.float32), partial_read=True)
    cluster = ClusterAccelerator(
        [(s1.host, s1.port), (s2.host, s2.port)], local_devices=_cpus(2)
    )
    try:
        cluster.setup_nodes(SRC)
        for it in range(3):
            want = y.host() + 2.0 * x.host()
            cluster.compute("saxpy", [x, y], 900, n, 64, values=(2.0,))
            np.testing.assert_allclose(y.host(), want, rtol=1e-6)
        shares = cluster.ranges_of(900)
        assert sum(shares) == n
        assert len(shares) == 3  # 2 remote nodes + mainframe
        assert len(cluster.compute_timing(900)) == 3
    finally:
        cluster.dispose()


def test_cluster_write_all_owned_by_mainframe(two_servers):
    """write_all arrays come back from the mainframe only — remote nodes
    must not race full-array writebacks."""
    s1, s2 = two_servers
    n = 1024
    out = ClArray(np.zeros(n, np.float32), read=False, write=True, write_all=True)
    cluster = ClusterAccelerator(
        [(s1.host, s1.port), (s2.host, s2.port)], local_devices=_cpus(2)
    )
    try:
        # write_all semantics: the kernel writes the WHOLE array regardless
        # of its assigned range; exactly one owner copy must win
        cluster.setup_nodes(
            "__kernel void fill(__global float* o, int n)"
            "{ for (int j = 0; j < n; j++) { o[j] = 5.0f; } }"
        )
        cluster.compute("fill", [out], 901, n, 64, values=(n,))
        # the mainframe's chips wrote the whole array: every element set
        np.testing.assert_array_equal(out.host(), np.full(n, 5.0, np.float32))
    finally:
        cluster.dispose()


def test_cluster_balancer_starved_node_recovers():
    bal = ClusterLoadBalancer(steps=[64, 64])
    ranges, rem = bal.equal_split(2048)
    # drive node 1 to its floor with terrible times, then make it fast
    for _ in range(12):
        ranges, rem = bal.rebalance(ranges, [1.0, 1000.0], 2048)
    assert ranges[1] >= 64  # probe share survives
    for _ in range(12):
        ranges, rem = bal.rebalance(ranges, [1000.0, 1.0], 2048)
    assert ranges[1] > 512  # starved node earned its work back


def test_node_failure_mid_run_fails_over_to_mainframe(two_servers):
    """Killing a server between computes must not lose results: the
    mainframe recomputes the dead node's share and the node is dropped."""
    s1, s2 = two_servers
    n = 4096
    x = ClArray(np.arange(n, dtype=np.float32), partial_read=True, read_only=True)
    y = ClArray(np.zeros(n, np.float32), partial_read=True)
    cluster = ClusterAccelerator(
        [(s1.host, s1.port), (s2.host, s2.port)], local_devices=_cpus(2)
    )
    try:
        cluster.setup_nodes(SRC)
        cluster.compute("saxpy", [x, y], 910, n, 64, values=(1.0,))
        np.testing.assert_allclose(y.host(), x.host(), rtol=1e-6)
        s2.stop()  # node dies between iterations
        cluster.compute("saxpy", [x, y], 910, n, 64, values=(1.0,))
        np.testing.assert_allclose(y.host(), 2.0 * x.host(), rtol=1e-6)
        assert len(cluster.clients) == 1  # dead node dropped
        # next compute re-splits across survivors and stays correct
        cluster.compute("saxpy", [x, y], 910, n, 64, values=(1.0,))
        np.testing.assert_allclose(y.host(), 3.0 * x.host(), rtol=1e-6)
    finally:
        cluster.dispose()


def test_concurrent_sessions_do_not_serialize(two_servers):
    """ISSUE 11 satellite: a second concurrent session SETUPs and
    COMPUTEs while the first session is mid-conversation AND mid-compute
    — per-connection session threads, nothing serializes them."""
    import threading

    s1, _ = two_servers
    n = 4096
    a = CruncherClient(s1.host, s1.port)
    b = CruncherClient(s1.host, s1.port)
    try:
        assert a.setup(SRC) == 2
        xa = ClArray(np.arange(n, dtype=np.float32), partial_read=True,
                     read_only=True)
        ya = ClArray(np.ones(n, np.float32), partial_read=True)
        errs: list = []

        def drive_a():
            try:
                for _ in range(6):
                    a.compute(["saxpy"], [xa, ya], 20, 0, n, 64,
                              values=(1.0,))
            except Exception as e:  # noqa: BLE001 - surfaced below
                errs.append(e)

        ta = threading.Thread(target=drive_a)
        ta.start()
        # B's whole lifecycle runs while A's session computes
        assert b.setup(SRC) == 2
        xb = ClArray(np.arange(n, dtype=np.float32), partial_read=True,
                     read_only=True)
        yb = ClArray(np.zeros(n, np.float32), partial_read=True)
        b.compute(["saxpy"], [xb, yb], 21, 0, n, 64, values=(3.0,))
        np.testing.assert_allclose(yb.host(), 3.0 * xb.host(), rtol=1e-6)
        ta.join(timeout=60)
        assert not ta.is_alive() and not errs, errs
        np.testing.assert_allclose(
            ya.host(), 1.0 + 6.0 * xa.host(), rtol=1e-6)
    finally:
        a.close()
        b.close()


def test_session_capacity_rejected_with_named_error():
    """Beyond max_sessions a connection is answered with a NAMED error
    (never a hang), and capacity frees when a session ends."""
    import time as _t

    from cekirdekler_tpu.errors import CekirdeklerError

    server = CruncherServer(devices=_cpus(2), max_sessions=1)
    try:
        a = CruncherClient(server.host, server.port)
        assert a.setup(SRC) == 2  # occupies the one session slot
        b = CruncherClient(server.host, server.port)
        with pytest.raises(CekirdeklerError, match="capacity"):
            b.setup(SRC)
        b.close()
        a.close()
        # the freed slot admits a new session (the accept loop reaps
        # dead session threads; poll briefly for the teardown)
        deadline = _t.monotonic() + 10.0
        while True:
            c = CruncherClient(server.host, server.port)
            try:
                assert c.setup(SRC) == 2
                break
            except CekirdeklerError:
                c.close()
                if _t.monotonic() > deadline:
                    raise
                _t.sleep(0.05)
        c.close()
    finally:
        server.stop()


def test_probe_finds_live_servers(two_servers):
    s1, s2 = two_servers
    live = ClusterAccelerator.probe(
        [(s1.host, s1.port), ("127.0.0.1", 1), (s2.host, s2.port)], timeout=0.3
    )
    assert (s1.host, s1.port) in live and (s2.host, s2.port) in live
    assert ("127.0.0.1", 1) not in live


def test_discover_scans_subnet(two_servers):
    """LAN discovery parity (findServer, ClusterAccelerator.cs:77-155):
    probing all 255 host addresses of a subnet finds the live server."""
    s1, _ = two_servers
    live = ClusterAccelerator.discover(s1.port, subnet="127.0.0", timeout=0.3)
    assert ("127.0.0.1", s1.port) in live


def test_cluster_across_real_processes():
    """A server in a SEPARATE python process (true serialization + GIL
    boundary, the reference's actual deployment shape): the cluster
    computes correctly against it plus the local mainframe."""
    import os
    import subprocess
    import sys
    import time as _t

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    proc = subprocess.Popen(
        [sys.executable, "-c", (
            "from cekirdekler_tpu.cluster import CruncherServer\n"
            "import cekirdekler_tpu as ct, sys, time\n"
            "s = CruncherServer(devices=ct.all_devices().cpus().subset(2))\n"
            "print(s.port, flush=True)\n"
            "time.sleep(120)\n"
        )],
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        stdout=subprocess.PIPE, text=True,
    )
    try:
        port = int(proc.stdout.readline().strip())
        n = 2048
        x = ClArray(np.arange(n, dtype=np.float32), partial_read=True, read_only=True)
        y = ClArray(np.ones(n, np.float32), partial_read=True)
        cluster = ClusterAccelerator([("127.0.0.1", port)], local_devices=_cpus(2))
        try:
            cluster.setup_nodes(SRC)
            for _ in range(2):
                cluster.compute(["saxpy"], [x, y], compute_id=1,
                                global_range=n, local_range=64, values=(2.0,))
            np.testing.assert_allclose(
                np.asarray(y), 1.0 + 2 * 2.0 * np.arange(n), rtol=1e-6
            )
        finally:
            cluster.dispose()
    finally:
        proc.kill()
        proc.wait(timeout=10)
