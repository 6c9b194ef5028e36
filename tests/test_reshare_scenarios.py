"""Resharing scenario battery: proactive rotation under LIVE duties on
the in-process simnet — the rotation lands mid-run via
SimCluster.apply_reshare (in-place registry + share swap, the simnet
mirror of app/run.Node.apply_reshare), duties keep completing with
zero missed slots, the group signature still verifies under the
ORIGINAL group key, and partials signed with pre-reshare shares are
rejected by the live verifier (stale-share unusability). Plus the
seeded chaos variant: a dealer crash mid-ceremony aborts every
participant cleanly and leaves NO torn key state on disk.
"""

import asyncio

import pytest

from charon_tpu import tbls
from charon_tpu.core import eth2data as d
from charon_tpu.core.eth2data import SignedData
from charon_tpu.core.types import Duty, DutyType, pubkey_to_bytes
from charon_tpu.crypto.g1g2 import g1_from_bytes, g1_to_bytes
from charon_tpu.dkg import reshare
from charon_tpu.tbls.python_impl import PythonImpl
from charon_tpu.testutil.simnet import build_cluster
from charon_tpu.testutil.waiting import wait_progress


@pytest.fixture(autouse=True)
def host_tbls():
    # native backend when available (test_simnet idiom) — realistic
    # signing latency keeps the live-rotation timing honest
    try:
        from charon_tpu.tbls.native_impl import NativeImpl

        tbls.set_implementation(NativeImpl())
    except ImportError:
        tbls.set_implementation(PythonImpl())
    yield
    tbls.set_implementation(PythonImpl())


def _slot_waves(beacon):
    """slot -> attestation broadcasts recorded by the mock beacon."""
    by_slot: dict[int, list] = {}
    for a in beacon.attestations:
        by_slot.setdefault(a.data.slot, []).append(a)
    return by_slot


def _prop_waves(beacon):
    by_slot: dict[int, list] = {}
    for proposal, sig in beacon.proposals:
        by_slot.setdefault(proposal.slot, []).append(sig)
    return by_slot


def _reshare_cluster(cluster, crash=(), timeout=5.0):
    """Run the resharing ceremony over the cluster's live key material
    (proactive rotation: same operators, same threshold, new shares).
    Returns {new_idx: [per-validator ReshareResult]}."""
    n, t = cluster.n, cluster.t
    v = len(cluster.group_pubkeys)
    cfg = reshare.ReshareConfig(
        old_indices=tuple(range(1, n + 1)),
        new_indices=tuple(range(1, n + 1)),
        t_old=t,
        t_new=t,
        num_validators=v,
    )
    shares_by_idx = {
        i: [
            int.from_bytes(cluster.share_keys[i - 1][gpk], "big")
            for gpk in cluster.group_pubkeys
        ]
        for i in range(1, n + 1)
    }
    old_pubshares = [
        {
            i: g1_from_bytes(cluster.pubshares_by_idx[i][gpk])
            for i in range(1, n + 1)
        }
        for gpk in cluster.group_pubkeys
    ]
    group_pks = [
        g1_from_bytes(pubkey_to_bytes(gpk)) for gpk in cluster.group_pubkeys
    ]
    net = reshare.MemReshareTransport(
        cfg.old_indices, timeout=timeout, crash=crash
    )

    async def ceremony():
        # return_exceptions: a crashed ceremony yields ReshareError per
        # participant instead of tearing the gather apart mid-abort
        return await asyncio.gather(
            *(
                reshare.run_reshare_parallel(
                    net.participant(i),
                    i,
                    cfg,
                    old_pubshares,
                    group_pks,
                    share_secrets=shares_by_idx[i],
                )
                for i in cfg.old_indices
            ),
            return_exceptions=True,
        )

    return cfg, ceremony


def _rotation_maps(cluster, results_by_idx):
    """ReshareResults -> the (share_keys, pubshares) maps
    SimCluster.apply_reshare swaps in."""
    new_share_keys, new_pubs = {}, {}
    for idx, res in results_by_idx.items():
        new_share_keys[idx] = {
            gpk: (r.secret_share % (1 << 256)).to_bytes(32, "big")
            for gpk, r in zip(cluster.group_pubkeys, res)
        }
        new_pubs[idx] = {
            gpk: g1_to_bytes(r.pubshares[idx])
            for gpk, r in zip(cluster.group_pubkeys, res)
        }
    return new_share_keys, new_pubs


def test_rotation_under_live_duties_zero_missed():
    async def run():
        # wide slots: the wave (attestation at a third of the slot, its
        # aggregation on the event loop: 0.4 CPU-s a slot for the four
        # nodes, my sandbox, PR 41) must END inside its slot on a tier-1
        # worker with a third of a core, or no quiet window for the swap
        # ever exists
        cluster = build_cluster(
            n=4, t=3, num_validators=1, slot_duration=4.0
        )
        beacon = cluster.beacon
        gpk = cluster.group_pubkeys[0]
        old_share_1 = cluster.share_keys[0][gpk]
        tasks = [
            asyncio.create_task(node.scheduler.run())
            for node in cluster.nodes
        ]
        try:
            # a slot's wave is DONE once all 4 nodes broadcast both the
            # attestation and the proposal aggregate for it — only then
            # is no duty in flight for that slot
            def full_wave_slots():
                atts, props = _slot_waves(beacon), _prop_waves(beacon)
                return {
                    s
                    for s, a in atts.items()
                    if len(a) >= 4 and len(props.get(s, ())) >= 4
                }

            sched = cluster.nodes[0].scheduler

            def clock_slot():
                return sched.clock.slot_at(sched._now())

            async def next_full_wave(after=-1, in_slot=False):
                # in_slot: only return while the wall clock is STILL in
                # the wave's slot — the next slot's proposer fires at
                # its start, so that is the quiet window for a swap
                def newest():
                    done = {s for s in full_wave_slots() if s > after}
                    if done and (not in_slot or max(done) == clock_slot()):
                        return [max(done)]  # a list: slot 0 is falsy

                return (
                    await wait_progress(
                        newest,
                        probe=lambda: (
                            len(beacon.attestations), len(beacon.proposals)
                        ),
                        what=f"a full wave after slot {after}"
                        + (" that ends inside its slot" if in_slot else ""),
                        poll=0.02,
                    )
                )[0]

            first_slot = await next_full_wave()

            # ceremony on the live shares, then the in-place swap
            # the ceremony's bigint math runs OFF the duty event loop
            # (operations.md: rotation under duties runs the ceremony on
            # a worker, only the swap touches the live node) — blocking
            # the loop for seconds WOULD miss slots, which is the point
            cfg, ceremony = _reshare_cluster(cluster)
            loop = asyncio.get_running_loop()
            outcomes = await asyncio.wait_for(
                loop.run_in_executor(None, lambda: asyncio.run(ceremony())),
                60,
            )
            assert not any(isinstance(o, Exception) for o in outcomes)
            results = dict(zip(cfg.old_indices, outcomes))

            # SWAP IN THE QUIET WINDOW (operations.md rotation procedure)
            # right after a wave FRESHLY aggregates — `after` must be the
            # newest already-complete slot, else we key on a wave that
            # finished ages ago and the swap lands mid-slot, mixing pre-
            # and post-rotation partials in parsigdb so the recombined
            # signature fails to verify (a missed duty)
            rotation_slot = await next_full_wave(
                after=max(full_wave_slots(), default=-1), in_slot=True
            )
            await cluster.apply_reshare(*_rotation_maps(cluster, results))

            # the cluster keeps completing duties on the NEW shares:
            # wait for two full post-rotation waves
            def post_waves():
                full = {s for s in full_wave_slots() if s > rotation_slot}
                return full if len(full) >= 2 else None

            post = await wait_progress(
                post_waves,
                probe=lambda: (
                    len(beacon.attestations), len(beacon.proposals)
                ),
                what=f"two full waves after the rotation in slot {rotation_slot}",
            )

            # ZERO missed duties: every slot between the first completed
            # wave and the last post-rotation wave that the four
            # schedulers ticked produced an aggregate (a slot a starved
            # event loop never gave them is no duty the rotation lost)
            waves = _slot_waves(beacon)
            given = cluster.slots_given()
            for s in range(first_slot, max(post) + 1):
                assert s in waves or s not in given, (
                    f"slot {s} produced no aggregate"
                )

            # the post-rotation aggregate verifies under the ORIGINAL
            # group pubkey — resharing never changed the group key
            att = waves[max(post)][0]
            root = SignedData("attestation", att).signing_root(
                cluster.fork, att.data.slot // beacon.slots_per_epoch
            )
            tbls.verify(pubkey_to_bytes(gpk), root, att.signature)
        finally:
            for node in cluster.nodes:
                node.scheduler.stop()
            await asyncio.gather(*tasks, return_exceptions=True)

        # stale-share unusability: a partial signed with the PRE-reshare
        # share no longer verifies against the live (rotated) registry
        # any node's verifier reads — sigagg never sees it aggregate
        verifier = cluster.nodes[0].parsigex.verifier
        duty = Duty(max(post) + 10, DutyType.ATTESTER)
        data = d.AttestationData(
            slot=duty.slot,
            index=0,
            beacon_block_root=b"\xaa" * 32,
            source=d.Checkpoint(0, b"\xbb" * 32),
            target=d.Checkpoint(1, b"\xcc" * 32),
        )
        unsigned = SignedData(
            "attestation", d.Attestation((True,), data)
        )
        root = unsigned.signing_root(
            cluster.fork, duty.slot // beacon.slots_per_epoch
        )
        impl = tbls.get_implementation()
        stale = d.ParSignedData(
            data=unsigned.with_signature(impl.sign(old_share_1, root)),
            share_idx=1,
        )
        assert not verifier.verify(duty, {gpk: stale})
        fresh = d.ParSignedData(
            data=unsigned.with_signature(
                impl.sign(cluster.share_keys[0][gpk], root)
            ),
            share_idx=1,
        )
        assert verifier.verify(duty, {gpk: fresh})

    asyncio.run(run())


def test_rotation_fires_rewarm_hook():
    async def run():
        cluster = build_cluster(
            n=4, t=3, num_validators=1, slot_duration=0.5, crypto_plane=True
        )
        try:
            warmups_before = [
                node.crypto_plane.warmups for node in cluster.nodes
            ]
            cfg, ceremony = _reshare_cluster(cluster)
            outcomes = await ceremony()
            assert not any(isinstance(o, Exception) for o in outcomes)
            results = dict(zip(cfg.old_indices, outcomes))
            await cluster.apply_reshare(*_rotation_maps(cluster, results))
            # the PR 6 rotation hook ran on every planed node: the new
            # pubshares were bulk-warmed before the next flush
            for node, before in zip(cluster.nodes, warmups_before):
                assert node.crypto_plane.warmups == before + 1
        finally:
            cluster.close()

    asyncio.run(run())


def test_chaos_crash_mid_reshare_aborts_cleanly(tmp_path):
    pytest.importorskip(
        "cryptography",
        reason="EIP-2335 keystores need the optional 'cryptography' package",
    )
    from charon_tpu.eth2util import keystore

    async def run():
        cluster = build_cluster(
            n=4, t=3, num_validators=1, slot_duration=0.5
        )
        beacon = cluster.beacon
        gpk = cluster.group_pubkeys[0]

        # each node's on-disk key state before the ceremony
        data_dirs = []
        for i in range(1, 5):
            ddir = tmp_path / f"node{i - 1}"
            keystore.store_keys(  # fixture  # lint: allow(secret-flow)
                [cluster.share_keys[i - 1][gpk]], ddir / "validator_keys"
            )
            data_dirs.append(ddir)
        snapshot = [
            sorted(p.name for p in (ddir / "validator_keys").iterdir())
            for ddir in data_dirs
        ]

        tasks = [
            asyncio.create_task(node.scheduler.run())
            for node in cluster.nodes
        ]
        try:
            # seeded crash: dealer 2 dies before publishing round 1
            cfg, ceremony = _reshare_cluster(
                cluster, crash=(2,), timeout=1.0
            )
            outcomes = await ceremony()
            assert outcomes and all(
                isinstance(o, reshare.ReshareError) for o in outcomes
            )

            # clean abort: nothing was written — no swapped keystores,
            # no staging debris, byte-identical key dirs
            for ddir, names in zip(data_dirs, snapshot):
                assert sorted(
                    p.name for p in (ddir / "validator_keys").iterdir()
                ) == names
                assert not (ddir / "validator_keys.pre-reshare").exists()
                assert not [
                    p for p in ddir.iterdir() if "stage" in p.name
                ]

            # the live cluster is untouched by the abort: duties keep
            # completing on the OLD shares
            await wait_progress(
                lambda: any(
                    len(atts) >= 4 for atts in _slot_waves(beacon).values()
                ),
                probe=lambda: len(beacon.attestations),
                what="a slot all four nodes broadcast on the old shares",
            )
        finally:
            for node in cluster.nodes:
                node.scheduler.stop()
            await asyncio.gather(*tasks, return_exceptions=True)

    asyncio.run(run())
