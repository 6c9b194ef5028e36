"""The engine behind a miss of the message cache (ISSUE 29).

`tbls/tpu_impl.MsgHashEngine` hashes a signing root to G2 in the native
library (GIL released) where the process can show that the library
agrees with the specification code, and in Python bigints (crypto/h2c)
otherwise. One function, two engines: every test here holds the served
point to `h2c.hash_to_g2`, which tests/test_sswu.py pins to RFC 9380's
known answers."""

from __future__ import annotations

import asyncio
import json
import os
import random
import subprocess
import sys
from pathlib import Path

import pytest

from charon_tpu.app import tracer
from charon_tpu.core.cryptoplane import FlushStats, SlotCoalescer
from charon_tpu.crypto import h2c
from charon_tpu.tbls import tpu_impl
from charon_tpu.tbls.python_impl import PythonImpl
from tests.test_cryptoplane import FakePlane, T
from tests.test_sswu import RFC_VECTORS

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def native_engine():
    """A fresh engine of a process that has the library (tracked under
    native/; `make -C native` rebuilds it)."""
    pytest.importorskip("charon_tpu.tbls.native_impl")
    engine = tpu_impl.MsgHashEngine()
    engine(b"resolve")
    assert engine.name == "native"
    return engine


# the RFC's five messages (J.10.1): test_sswu holds h2c to the RFC's
# points under the RFC's DST; the library knows the signature DST alone,
# so the two engines meet on the messages under that one
@pytest.mark.parametrize(
    "msg", [m for m, _, _ in RFC_VECTORS], ids=lambda m: f"len{len(m)}"
)
def test_native_engine_equals_the_specification_code_on_rfc_messages(
    native_engine, msg
):
    assert native_engine(msg) == h2c.hash_to_g2(msg)


def test_native_engine_equals_the_specification_code_on_seeded_roots(
    native_engine,
):
    rng = random.Random(29)
    roots = [rng.randbytes(32) for _ in range(64)]
    before = native_engine.counts()
    assert [native_engine(r) for r in roots] == [
        h2c.hash_to_g2(r) for r in roots
    ]
    after = native_engine.counts()
    assert after["native"] - before["native"] == 64
    assert after["python"] == 0


def _in_a_fresh_process(script: str, **env) -> dict:
    """Run `script` where nothing has touched the library yet; its last
    stdout line is the JSON it reports."""
    out = subprocess.run(
        [sys.executable, "-c", script],
        cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env},
        capture_output=True,
        text=True,
        timeout=240,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


_POOL_SCRIPT = """
import concurrent.futures, json, random, sys, threading
from charon_tpu.crypto import h2c
from charon_tpu.tbls import tpu_impl

rng = random.Random(2929)
roots = [rng.randbytes(32) for _ in range(256)]
cache = tpu_impl.make_point_cache(tpu_impl._decode_msg_point, maxsize=512)
gate = threading.Barrier(8)

def worker(k):
    gate.wait(timeout=60)  # all eight are the first into the library
    # each root from two threads: concurrent misses of one key too
    mine = roots[32 * k : 32 * k + 32] + roots[32 * ((k + 1) % 8) :][:32]
    return [(r, cache(r)) for r in mine]

old = sys.getswitchinterval()
sys.setswitchinterval(1e-5)
try:
    with concurrent.futures.ThreadPoolExecutor(max_workers=8) as pool:
        got = [kv for f in [pool.submit(worker, k) for k in range(8)]
               for kv in f.result(timeout=120)]
finally:
    sys.setswitchinterval(old)
want = {r: h2c.hash_to_g2(r) for r in roots}
engine = tpu_impl._decode_msg_point
print(json.dumps({
    "lookups": len(got),
    "wrong": sum(pt != want[r] for r, pt in got),
    "engine": engine.name,
    "counts": engine.counts(),
    "misses": cache.cache_info().misses,
    "entries": cache.cache_info().currsize,
}))
"""


def test_eight_pool_threads_first_into_the_library_agree_with_python():
    """The decode pool's threads are the first callers of a process:
    the engine's guarded first call (load, probe, the library's
    unguarded ensure_init) happens once, and all 512 lookups of 256
    roots through a fresh PointCache equal the specification code."""
    pytest.importorskip("charon_tpu.tbls.native_impl")
    got = _in_a_fresh_process(_POOL_SCRIPT)
    assert got["lookups"] == 512 and got["wrong"] == 0
    assert got["engine"] == "native" and got["entries"] == 256
    # every miss was hashed, and by the native engine (a root looked up
    # by two threads at once may miss twice: PointCache's contract)
    assert got["counts"] == {"native": got["misses"], "python": 0}
    assert 256 <= got["misses"] <= 512


_MISSING_SCRIPT = """
import json
from charon_tpu.app.metrics import ClusterMetrics
from charon_tpu.crypto import h2c
from charon_tpu.tbls import tpu_impl

roots = [bytes([i]) * 32 for i in range(3)]
same = [tpu_impl._cached_msg_point(r) == h2c.hash_to_g2(r) for r in roots]
engine = tpu_impl._decode_msg_point
metrics = ClusterMetrics("0xhash", "c", "node0")
metrics.observe_point_caches()
exported = {
    s.labels["engine"]: s.value
    for fam in metrics.registry.collect()
    if fam.name == "tpu_point_cache_message_hashed"
    for s in fam.samples
}
print(json.dumps({"same": same, "engine": engine.name,
                  "counts": engine.counts(), "exported": exported}))
"""


def test_without_the_library_the_decoder_answers_from_python(tmp_path):
    """CHARON_NATIVE_LIB at a file that is not there (a host where
    `make -C native` never ran): the same points, from Python, and the
    counter and its exported family say so."""
    got = _in_a_fresh_process(
        _MISSING_SCRIPT, CHARON_NATIVE_LIB=str(tmp_path / "absent.so")
    )
    assert got["same"] == [True, True, True]
    assert got["engine"] == "python"
    assert got["counts"] == {"native": 0, "python": 3}
    assert got["exported"] == {"native": 0.0, "python": 3.0}


def test_a_library_that_disagrees_is_not_used(monkeypatch):
    """Answers that differ on the probe: Python for the life of the
    engine, and still the specification's point."""
    native_impl = pytest.importorskip("charon_tpu.tbls.native_impl")
    wrong = native_impl.NativeImpl().hash_to_g2_bytes(b"another message")
    monkeypatch.setattr(
        native_impl.NativeImpl, "hash_to_g2_bytes", lambda self, data: wrong
    )
    engine = tpu_impl.MsgHashEngine()
    root = b"\x29" * 32
    assert engine(root) == h2c.hash_to_g2(root)
    assert engine.name == "python"
    assert engine.counts() == {"native": 0, "python": 1}


def _decode_spans(t: tracer.Tracer) -> list:
    return [s for s in t.spans if s.name == "cryptoplane.decode"]


def test_the_first_jobs_decode_span_counts_the_roots_it_hashed():
    """A SlotCoalescer.verify of 32 roots the process has never seen
    leaves msg_hashed 32 and engine native on its decode span; the next
    job of the wave (a peer's set on the same roots) leaves 0."""
    pytest.importorskip("charon_tpu.tbls.native_impl")
    impl = PythonImpl()
    sk = impl.generate_secret_key()
    pk = impl.secret_to_public_key(sk)
    sig = impl.sign(sk, b"any")  # the fake plane verifies nothing
    tag = os.urandom(16)  # roots no other test of this process hashed
    items = [(pk, tag + i.to_bytes(16, "big"), sig) for i in range(32)]

    t = tracer.Tracer()
    stats: list[FlushStats] = []
    plane = SlotCoalescer(
        FakePlane(T),
        window=0.01,
        decode_workers=4,
        stats_hook=tracer.plane_span_bridge(t, inner_hook=stats.append),
    )
    try:
        assert asyncio.run(plane.verify(items)) == [True] * 32
        first = _decode_spans(t)
        assert asyncio.run(plane.verify(items)) == [True] * 32
        second = _decode_spans(t)[len(first) :]
    finally:
        plane.close()
    # two chunks of DECODE_CHUNK lanes, one span per stretch
    assert sum(s.attrs["chunks"] for s in first) == 2
    assert sum(s.attrs["msg_hashed"] for s in first) == 32
    assert {s.attrs["engine"] for s in first} == {"native"}
    assert second and [s.attrs["msg_hashed"] for s in second] == [0] * len(
        second
    )
    assert sum(stats[0].decode_hashed) == 32 and len(stats[0].decode_hashed) == 2
    assert sum(stats[1].decode_hashed) == 0
    assert stats[0].msg_hash_engine == stats[1].msg_hash_engine == "native"


def test_the_bridge_sums_the_hashes_of_a_stretch():
    """Chunks that overlap are one decode span: its msg_hashed is their
    sum. A brief that carries no counts (a remote flush) reads 0 and
    names no engine."""
    base = dict(
        jobs=2, lanes=40, flush_seconds=0.5, window=0.3, inflight=1,
        pad_lanes=None, padded_lanes=None, decode_queue_seconds=(),
        decode_spans=((10.05, 10.2), (10.0, 10.1), (10.6, 10.7)),
        parents=(("c" * 32, "1" * 16),),
    )
    t = tracer.Tracer()
    tracer.plane_span_bridge(t)(
        FlushStats(**base, decode_hashed=(15, 16, 1), msg_hash_engine="native")
    )
    assert [
        (s.start, s.end, s.attrs["chunks"], s.attrs["msg_hashed"], s.attrs["engine"])
        for s in _decode_spans(t)
    ] == [(10.0, 10.2, 2, 31, "native"), (10.6, 10.7, 1, 1, "native")]
    t = tracer.Tracer()
    tracer.plane_span_bridge(t)(FlushStats(**base))
    assert [s.attrs["msg_hashed"] for s in _decode_spans(t)] == [0, 0]
    assert all("engine" not in s.attrs for s in _decode_spans(t))
