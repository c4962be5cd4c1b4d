"""Driver/task services: HMAC auth + NIC discovery.

Mirrors the reference's service-layer test intent (driver/task
registration, interface matching, secret checks) with multi-NIC fakes.
"""

import json
import threading
import urllib.error
import urllib.request

import pytest

from horovod_tpu.run import secret
from horovod_tpu.run.discovery import (DriverService, PingServer, TaskAgent,
                                       discover, host_hash,
                                       local_interfaces, probe)
from horovod_tpu.run.rendezvous import (AUTH_HEADER, KVStoreServer, kv_get,
                                        kv_put, kv_wait)


def test_secret_sign_verify():
    key = secret.make_secret_key()
    sig = secret.sign(key, "PUT", "/a/b", b"payload")
    assert secret.verify(key, "PUT", "/a/b", b"payload", sig)
    assert not secret.verify(key, "PUT", "/a/b", b"tampered", sig)
    assert not secret.verify(key, "GET", "/a/b", b"payload", sig)
    assert not secret.verify(key, "PUT", "/a/c", b"payload", sig)
    assert not secret.verify(key, "PUT", "/a/b", b"payload", None)
    key2 = secret.decode_key(secret.encode_key(key))
    assert key2 == key


def test_kv_rejects_unauthenticated():
    key = secret.make_secret_key()
    kv = KVStoreServer(auth_key=key)
    port = kv.start()
    try:
        # unsigned PUT → 403, store untouched
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/x", data=b"evil", method="PUT")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=5)
        assert ei.value.code == 403
        assert kv.get("x") is None

        # wrong-key PUT → 403
        with pytest.raises(urllib.error.HTTPError) as ei:
            kv_put("127.0.0.1", port, "x", b"evil",
                   auth_key=secret.make_secret_key())
        assert ei.value.code == 403

        # signed round trip works
        kv_put("127.0.0.1", port, "x", b"good", auth_key=key)
        assert kv_get("127.0.0.1", port, "x", auth_key=key) == b"good"

        # unsigned GET is rejected even for existing keys
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"http://127.0.0.1:{port}/x", timeout=5)
        assert ei.value.code == 403
    finally:
        kv.stop()


def test_kv_open_when_unkeyed():
    kv = KVStoreServer()
    port = kv.start()
    try:
        kv_put("127.0.0.1", port, "k", b"v")
        assert kv_get("127.0.0.1", port, "k") == b"v"
    finally:
        kv.stop()


def test_ping_server_and_probe():
    key = secret.make_secret_key()
    srv = PingServer("task-0", key, host="127.0.0.1")
    try:
        addrs = {"lo": [("127.0.0.1", srv.port)]}
        local = {"lo": [("127.0.0.1", srv.port)]}
        got = probe(addrs, key, "task-0", match_intf=True,
                    local_addrs=local, timeout=2.0)
        assert got == {"lo": [("127.0.0.1", srv.port)]}

        # wrong service name → filtered
        assert probe(addrs, key, "task-9", local_addrs=local,
                     timeout=2.0) == {}

        # wrong key → server drops the frame, nothing reachable
        assert probe(addrs, secret.make_secret_key(), "task-0",
                     local_addrs=local, timeout=1.0, retries=1) == {}
    finally:
        srv.shutdown()


def test_probe_match_intf_filters_nat():
    """A candidate reached through a DIFFERENT interface than claimed is
    rejected (reference network.py match_intf), simulated by giving the
    prober a local view where 'fakenic' does not own 127.0.0.1."""
    key = secret.make_secret_key()
    srv = PingServer("task-0", key, host="127.0.0.1")
    try:
        addrs = {"fakenic": [("127.0.0.1", srv.port)]}
        local = {"fakenic": [("192.0.2.1", 0)]}  # TEST-NET, not ours
        assert probe(addrs, key, "task-0", match_intf=True,
                     local_addrs=local, timeout=2.0) == {}
    finally:
        srv.shutdown()


def test_local_interfaces_real():
    ifs = local_interfaces(port=1234)
    assert "lo" in ifs
    assert ("127.0.0.1", 1234) in ifs["lo"]
    with pytest.raises(RuntimeError):
        local_interfaces(nic="does-not-exist-0")


def test_discovery_end_to_end_multi_nic():
    """3 fake hosts, each with a routable 'eth0' (loopback-backed) and an
    unroutable 'docker0'; the ring probe + intersection must elect
    exactly eth0, and host hashes must group ranks."""
    key = secret.make_secret_key()
    kv = KVStoreServer(auth_key=key)
    port = kv.start()
    try:
        n = 3
        fake = {"eth0": [("127.0.0.1", 0)],
                "docker0": [("192.0.2.77", 0)]}  # unroutable TEST-NET
        agents = [TaskAgent(i, n, "127.0.0.1", port, key,
                            addresses=dict(fake),
                            host_salt="hostA" if i < 2 else "hostB")
                  for i in range(n)]
        try:
            for a in agents:
                a.register()
            driver = DriverService(n, "127.0.0.1", port, key)
            regs = driver.wait_for_registrations(timeout=20)
            assert set(regs) == {0, 1, 2}
            threads = [threading.Thread(target=a.run_ring_probe,
                                        kwargs={"timeout": 20})
                       for a in agents]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            common = driver.wait_for_probes(timeout=20)
            assert common == ["eth0"]

            groups = driver.host_hash_indices(regs)
            assert sorted(groups.values()) == [[0, 1], [2]]
            assert host_hash("hostA") != host_hash("hostB")

            # every task can read the verdict back
            assert agents[0].common_interfaces(timeout=5) == ["eth0"]
        finally:
            for a in agents:
                a.shutdown()
    finally:
        kv.stop()


def test_discover_helper():
    key = secret.make_secret_key()
    kv = KVStoreServer(auth_key=key)
    port = kv.start()
    try:
        common, groups = discover(2, "127.0.0.1", port, key,
                                  host_salts={0: "h0", 1: "h1"})
        # real interfaces on this machine: loopback is always mutual
        assert "lo" in common
        assert sorted(groups.values()) == [[0], [1]]
    finally:
        kv.stop()


def test_ssh_secret_not_in_argv():
    """The per-run key must never appear in the ssh command line; it ships
    over stdin instead (world-readable /proc/*/cmdline)."""
    from horovod_tpu.run import launcher
    key_hex = secret.encode_key(secret.make_secret_key())
    env = {secret.SECRET_ENV: key_hex, "HOROVOD_RANK": "0"}
    cmd, proc_env, payload = launcher.build_command(
        "remotehost", ["python", "train.py"], env)
    joined = " ".join(cmd)
    assert key_hex not in joined
    assert payload == (key_hex + "\n").encode()
    assert f"read -r {secret.SECRET_ENV}" in joined
    assert "HOROVOD_RANK=0" in joined

    # local slots keep it in the process env (not in any argv)
    cmd2, env2, payload2 = launcher.build_command(
        "localhost", ["python", "train.py"], env)
    assert payload2 is None and env2[secret.SECRET_ENV] == key_hex


def test_driver_liveness_aborts_on_dead_task():
    key = secret.make_secret_key()
    kv = KVStoreServer(auth_key=key)
    port = kv.start()
    try:
        driver = DriverService(1, "127.0.0.1", port, key,
                               liveness=lambda: False)
        with pytest.raises(RuntimeError, match="discovery task exited"):
            driver.wait_for_registrations(timeout=30)
    finally:
        kv.stop()
