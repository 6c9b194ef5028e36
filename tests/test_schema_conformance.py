"""Beacon-API schema conformance of the validator-API HTTP surface.

No VC binary ships in this image, so the reference's real-client
integration tier (Teku against charon's vapi, ref: testutil/integration,
testutil/compose) is stood in for by STRICT OpenAPI-shape validation:
the full duty matrix runs over HTTP with a client that asserts every
request body and response against the published beacon-API shapes
(testutil/schemas.py) — quoted uints, exact hex widths, required fields,
container structure. Any violation fails the duty mid-flight.
"""

import asyncio

import pytest

from charon_tpu import tbls
from charon_tpu.tbls.python_impl import PythonImpl
from charon_tpu.testutil import schemas
from charon_tpu.testutil.simnet import build_cluster
from charon_tpu.testutil.vapiclient import SchemaCheckedVapiClient

from charon_tpu.testutil.waiting import wait_for_broadcasts

from test_vapi_http_e2e import (
    ALL_DUTIES_SLOT,
    _start_http,
    _stop_http,
    _wire_http_vmocks,
)


@pytest.fixture(autouse=True)
def host_tbls():
    try:
        from charon_tpu.tbls.native_impl import NativeImpl

        tbls.set_implementation(NativeImpl())
    except ImportError:
        tbls.set_implementation(PythonImpl())
    yield
    tbls.set_implementation(PythonImpl())


def test_all_duties_schema_conformant():
    """Attester, proposer, aggregator, sync-committee, registration and
    exit flows complete with every HTTP exchange schema-validated."""

    async def run():
        cluster = build_cluster(
            n=4,
            t=3,
            num_validators=1,
            slot_duration=ALL_DUTIES_SLOT,
            wire_vmock=False,
        )
        routers, clients, vmocks = await _start_http(
            cluster, client_cls=SchemaCheckedVapiClient
        )
        _wire_http_vmocks(cluster, vmocks)

        beacon = cluster.beacon
        tasks = [
            asyncio.create_task(node.scheduler.run())
            for node in cluster.nodes
        ]
        try:
            pubkey = cluster.group_pubkeys[0]
            for vm in vmocks:
                await vm.register(pubkey)
                await vm.exit(pubkey, epoch=0)

            await wait_for_broadcasts(beacon, want=4)

            # metadata surface a stock VC reads at startup — validated
            # through the same schema-checked client
            c = clients[0]
            await c.get_validators()
            await c.attester_duties(0, list(range(len(cluster.group_pubkeys))))
            await c.proposer_duties(0)
            await c.node_version()
            for path in (
                "/eth/v1/node/syncing",
                "/eth/v1/beacon/genesis",
                "/eth/v1/beacon/states/head/fork",
            ):
                await c._get(path)
        finally:
            for node in cluster.nodes:
                node.scheduler.stop()
            await asyncio.gather(*tasks, return_exceptions=True)
            checked = sum(c.checked for c in clients)
            unmatched = {u for c in clients for u in c.unmatched}
            await _stop_http(routers, clients)

        # every exchange type the duty matrix produces was validated,
        # and nothing fell through the route table unvalidated
        assert checked >= 40, f"only {checked} exchanges validated"
        assert not unmatched, f"unvalidated endpoints: {sorted(unmatched)}"

    asyncio.run(run())


def test_schema_validator_rejects_bad_shapes():
    """The validator itself must have teeth: wrong formats and missing
    fields are caught with precise paths."""
    ok = {
        "slot": "3",
        "index": "0",
        "beacon_block_root": "0x" + "00" * 32,
        "source": {"epoch": "0", "root": "0x" + "11" * 32},
        "target": {"epoch": "1", "root": "0x" + "22" * 32},
    }
    schemas.validate(schemas.ATT_DATA, ok, "att")

    bad_cases = [
        ({**ok, "slot": 3}, "unquoted int"),  # integers must be strings
        ({**ok, "beacon_block_root": "0x1234"}, "short hex"),
        ({k: v for k, v in ok.items() if k != "target"}, "missing field"),
        ({**ok, "source": {"epoch": "0"}}, "missing nested field"),
    ]
    for bad, label in bad_cases:
        with pytest.raises(schemas.SchemaError):
            schemas.validate(schemas.ATT_DATA, bad, label)

    # route table resolves the paths the client actually uses
    assert schemas.find_route("GET", "/eth/v3/validator/blocks/42")
    assert schemas.find_route("POST", "/eth/v2/beacon/blocks")
    assert schemas.find_route("GET", "/eth/v1/beacon/states/head/validators")
    assert schemas.find_route("POST", "/eth/v1/validator/duties/attester/7")
    assert schemas.find_route("GET", "/nope/nothing") is None
