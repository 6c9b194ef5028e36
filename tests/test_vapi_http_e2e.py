"""End-to-end duty flows driven ONLY over the beacon-API HTTP surface.

A 4-node (t=3) in-process cluster where every node's ValidatorAPI is
served by its own aiohttp router and driven by an HttpValidatorMock that
speaks nothing but HTTP — attester, proposer (randao via v3 blocks query
param), aggregator (beacon-committee selections -> aggregate ->
aggregate_and_proofs), sync-committee (message + selections + contribution
+ contribution_and_proofs), builder registration, and voluntary exit
(ref: core/validatorapi/router.go:97-253 endpoint set;
testutil/integration/simnet_test.go duty assertions).
"""

import asyncio

import pytest

from charon_tpu import tbls
from charon_tpu.core.eth2data import SignedData
from charon_tpu.core.scheduler import DutyDefinition
from charon_tpu.core.types import DutyType, pubkey_to_bytes
from charon_tpu.core.vapi_http import VapiRouter
from charon_tpu.tbls.python_impl import PythonImpl
from charon_tpu.testutil.simnet import build_cluster
from charon_tpu.testutil.validatormock import HttpValidatorMock
from charon_tpu.testutil.vapiclient import HttpVapiClient
from charon_tpu.testutil.waiting import wait_for_broadcasts


@pytest.fixture(autouse=True)
def host_tbls():
    try:
        from charon_tpu.tbls.native_impl import NativeImpl

        tbls.set_implementation(NativeImpl())
    except ImportError:
        tbls.set_implementation(PythonImpl())
    yield
    tbls.set_implementation(PythonImpl())


async def _start_http(cluster, client_cls=HttpVapiClient):
    """One router + HTTP client + HTTP vmock per node."""
    routers, clients, vmocks = [], [], []
    validators = {pk: i for i, pk in enumerate(cluster.group_pubkeys)}
    for node in cluster.nodes:
        router = VapiRouter(
            node.vapi,
            beacon=cluster.beacon,
            validators=validators,
            genesis_time=cluster.beacon.genesis_time,
            slots_per_epoch=cluster.beacon.slots_per_epoch,
            slot_duration=cluster.beacon.slot_duration,
        )
        port = await router.start()
        client = client_cls(f"http://127.0.0.1:{port}", validators)
        vmock = HttpValidatorMock(
            client=client,
            share_keys=cluster.share_keys[node.share_idx - 1],
            validators=validators,
            fork=cluster.fork,
            slots_per_epoch=cluster.beacon.slots_per_epoch,
        )
        routers.append(router)
        clients.append(client)
        vmocks.append(vmock)
    return routers, clients, vmocks


async def _stop_http(routers, clients):
    for c in clients:
        await c.close()
    for r in routers:
        await r.stop()


def _wire_http_vmocks(cluster, vmocks):
    """Subscribe each node's HTTP vmock to its scheduler duties (replaces
    the in-process vmock wiring for this test)."""
    for node, vmock in zip(cluster.nodes, vmocks):

        async def on_duty(duty, defs, _vm=vmock):
            if duty.type == DutyType.ATTESTER:
                await _vm.attest(duty.slot, defs)
            elif duty.type == DutyType.PROPOSER:
                for pubkey in defs:
                    asyncio.create_task(_vm.propose(duty.slot, pubkey))
            elif duty.type == DutyType.AGGREGATOR:
                asyncio.create_task(_vm.aggregate(duty.slot, defs))
            elif duty.type == DutyType.SYNC_MESSAGE:
                asyncio.create_task(_vm.sync_message(duty.slot, defs))
            elif duty.type == DutyType.SYNC_CONTRIBUTION:
                asyncio.create_task(_vm.sync_contribution(duty.slot, defs))

        node.scheduler.subscribe_duties(on_duty)


# The slot a LOADED tier-1 worker serves the full duty matrix in. Every
# duty kind fires every slot here, and four nodes' BLS runs on the one
# event loop: 1.9 CPU-s a slot (768 native verify_batch calls of 13.5 ms
# in 5.4 slots: cProfile, my sandbox, PR 41); a worker under six-fold
# load has a third of a core. A loop that falls behind its slots never
# catches up, and a flow that comes later than the scheduler keeps an
# epoch's duty definitions (two epochs) is refused for ever: 404 "no
# attester duty".
ALL_DUTIES_SLOT = 6.0


def test_http_e2e_all_duties():
    async def run():
        cluster = build_cluster(
            n=4,
            t=3,
            num_validators=1,
            slot_duration=ALL_DUTIES_SLOT,
            wire_vmock=False,
        )
        routers, clients, vmocks = await _start_http(cluster)
        _wire_http_vmocks(cluster, vmocks)

        beacon = cluster.beacon
        tasks = [
            asyncio.create_task(node.scheduler.run())
            for node in cluster.nodes
        ]
        try:
            # registration + exit are one-shot duties; fire them over HTTP
            pubkey = cluster.group_pubkeys[0]
            for vm in vmocks:
                await vm.register(pubkey)
                await vm.exit(pubkey, epoch=0)

            await wait_for_broadcasts(beacon, want=4)
        finally:
            for node in cluster.nodes:
                node.scheduler.stop()
            await asyncio.gather(*tasks, return_exceptions=True)
            await _stop_http(routers, clients)

        group_pk = cluster.group_pubkeys[0]
        spe = beacon.slots_per_epoch

        # attestations: one group signature, verifies under the group key
        att = beacon.attestations[0]
        assert len({a.signature for a in beacon.attestations[:4]}) == 1
        root = SignedData("attestation", att).signing_root(
            cluster.fork, att.data.slot // spe
        )
        tbls.verify(pubkey_to_bytes(group_pk), root, att.signature)

        # proposals
        proposal, psig = beacon.proposals[0]
        assert len({s for _, s in beacon.proposals[:4]}) == 1
        proot = SignedData("block", proposal).signing_root(
            cluster.fork, proposal.slot // spe
        )
        tbls.verify(pubkey_to_bytes(group_pk), proot, psig)

        # aggregates
        agg, asig = beacon.aggregates[0]
        aroot = SignedData("aggregate_and_proof", agg).signing_root(
            cluster.fork, agg.aggregate.data.slot // spe
        )
        tbls.verify(pubkey_to_bytes(group_pk), aroot, asig)

        # sync messages
        sm = beacon.sync_messages[0]
        sroot = SignedData("sync_message", sm).signing_root(
            cluster.fork, sm.slot // spe
        )
        tbls.verify(pubkey_to_bytes(group_pk), sroot, sm.signature)

        # contributions
        cap, csig = beacon.contributions[0]
        croot = SignedData("contribution_and_proof", cap).signing_root(
            cluster.fork, cap.contribution.slot // spe
        )
        tbls.verify(pubkey_to_bytes(group_pk), croot, csig)

        # registrations
        reg, rsig = beacon.registrations[0]
        rroot = SignedData("registration", reg).signing_root(cluster.fork, 0)
        tbls.verify(pubkey_to_bytes(group_pk), rroot, rsig)

        # exits
        ex, esig = beacon.exits[0]
        eroot = SignedData("exit", ex).signing_root(cluster.fork, 0)
        tbls.verify(pubkey_to_bytes(group_pk), eroot, esig)

    asyncio.run(run())


def test_http_metadata_endpoints():
    async def run():
        cluster = build_cluster(n=4, t=3, num_validators=2, slot_duration=5.0)
        routers, clients, _ = await _start_http(cluster)
        try:
            c = clients[0]
            assert (await c.node_version()).startswith("charon-tpu/")
            vals = await c.get_validators()
            assert len(vals) == 2
            # lookup by this node's pubshare maps to the group validator
            # (ref: validatorapi.go:1080 pubshare<->group mapping)
            node = cluster.nodes[0]
            pubshare = next(iter(node.vapi.pubshares.values()))
            vals = await c.get_validators(ids=["0x" + pubshare.hex()])
            assert len(vals) == 1
            assert vals[0]["validator"]["pubkey"] == "0x" + pubshare.hex()
            duties = await c.attester_duties(0, [0, 1])
            assert duties  # deterministic beaconmock duties
            pduties = await c.proposer_duties(0)
            assert pduties
        finally:
            await _stop_http(routers, clients)

    asyncio.run(run())
