"""obolapi client vs mock server + stacksnipe process detection
(ref: app/obolapi/api.go, testutil/obolapimock, app/stacksnipe).
"""

from __future__ import annotations

import asyncio
import os

import pytest

from charon_tpu import tbls
from charon_tpu.app.obolapi import ObolApiClient
from charon_tpu.app.stacksnipe import KNOWN_BINARIES, StackSniper, snipe
from charon_tpu.tbls.python_impl import PythonImpl
from charon_tpu.testutil.obolapimock import ObolApiMock
from charon_tpu.testutil.waiting import wait_until


@pytest.fixture(autouse=True)
def host_tbls():
    try:
        from charon_tpu.tbls.native_impl import NativeImpl

        tbls.set_implementation(NativeImpl())
    except ImportError:
        tbls.set_implementation(PythonImpl())
    yield
    tbls.set_implementation(PythonImpl())


def test_obolapi_lock_publish_and_exit_aggregation():
    async def run():
        mock = ObolApiMock(threshold=3)
        port = await mock.start()
        client = ObolApiClient(f"http://127.0.0.1:{port}")

        # lock publish (ref: dkg.go:118-128 optional publish)
        class FakeLock:
            def to_json(self):
                return {"name": "c", "lock_hash": "0xabc"}

        await client.publish_lock(FakeLock())
        assert mock.locks == [{"name": "c", "lock_hash": "0xabc"}]

        # partial exits aggregate at threshold
        sk = tbls.generate_secret_key()
        pk = tbls.secret_to_public_key(sk)
        shares = tbls.threshold_split(sk, 4, 3)
        lock_hash = b"\x07" * 32
        msg = b"exit-root"
        pubkey_hex = "0x" + pk.hex()
        for idx in (1, 2):
            await client.submit_partial_exit(
                lock_hash, idx, pubkey_hex, 5, tbls.sign(shares[idx], msg)
            )
        assert await client.fetch_full_exit(lock_hash, pubkey_hex) is None
        await client.submit_partial_exit(
            lock_hash, 3, pubkey_hex, 5, tbls.sign(shares[3], msg)
        )
        full = await client.fetch_full_exit(lock_hash, pubkey_hex)
        assert full is not None
        tbls.verify(pk, msg, bytes.fromhex(full["signature"][2:]))
        await mock.stop()

    asyncio.run(run())


def test_stacksnipe_detects_known_binary(tmp_path):
    # fabricate a /proc with one known and one unknown process
    p1 = tmp_path / "101"
    p1.mkdir()
    (p1 / "cmdline").write_bytes(b"/usr/bin/lighthouse\x00bn\x00")
    p2 = tmp_path / "202"
    p2.mkdir()
    (p2 / "cmdline").write_bytes(b"/usr/bin/unrelated\x00")
    (tmp_path / "not-a-pid").mkdir()

    found = snipe(tmp_path)
    assert found == {"lighthouse": [101]}


def test_stacksnipe_periodic_reports(tmp_path):
    p = tmp_path / "7"
    p.mkdir()
    (p / "cmdline").write_bytes(b"teku\x00")

    async def run():
        reports = []
        sniper = StackSniper(
            interval=0.01, on_report=reports.append, proc_root=tmp_path
        )
        sniper.start()
        await wait_until(
                lambda: reports,
                "the sniper's first report",
            )
        await sniper.stop()
        assert reports[0] == {"teku": [7]}

    asyncio.run(run())


def test_stacksnipe_real_proc_does_not_crash():
    snipe("/proc")  # whatever is running, must not raise


def test_stacksnipe_gauge_hook_zeroes_departed_binaries(tmp_path):
    """ISSUE 19 satellite: the run.py wiring publishes each scan as
    stack_colocated_processes{binary} and zeroes binaries that vanished
    between scans (a stale non-zero gauge would page forever)."""
    from charon_tpu.app.metrics import ClusterMetrics

    metrics = ClusterMetrics("0xdead", "test", "node0")
    hook = metrics.stacksnipe_hook()

    hook({"lighthouse": [101, 102], "teku": [7]})
    rendered = metrics.render().decode()
    assert 'binary="lighthouse"' in rendered
    lh = [
        line
        for line in rendered.splitlines()
        if line.startswith("stack_colocated_processes")
        and 'binary="lighthouse"' in line
    ]
    assert lh and lh[0].endswith("2.0")

    hook({"teku": [7]})  # lighthouse exited: its gauge must drop to 0
    rendered = metrics.render().decode()
    lh = [
        line
        for line in rendered.splitlines()
        if line.startswith("stack_colocated_processes")
        and 'binary="lighthouse"' in line
    ]
    assert lh and lh[0].endswith("0.0")

    # end-to-end over a fake /proc: sniper loop feeds the same hook
    p = tmp_path / "9"
    p.mkdir()
    (p / "cmdline").write_bytes(b"/usr/local/bin/prysm\x00--datadir\x00x\x00")

    async def run():
        sniper = StackSniper(interval=0.01, on_report=hook, proc_root=tmp_path)
        sniper.start()
        await asyncio.sleep(0.05)
        await sniper.stop()

    asyncio.run(run())
    rendered = metrics.render().decode()
    assert 'binary="prysm"' in rendered
