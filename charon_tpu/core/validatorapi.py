"""ValidatorAPI: the beacon-node facade served to the downstream VC.

Mirrors ref: core/validatorapi/validatorapi.go — maps group pubkeys to this
node's pubshares (validatorapi.go:1080,1167), serves duty data with
blocking awaits against DutyDB, verifies every incoming partial signature
against the node's pubshare (validatorapi.go:1213) and pushes it into
ParSigDB as a ParSignedData.

This module is the transport-agnostic component; the HTTP router
(charon_tpu/core/vapi_http.py) exposes it as the eth2 beacon API the same
way ref core/validatorapi/router.go does. Partial-signature verification
is batched: one device call per submission set.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Awaitable, Callable, Sequence

from charon_tpu import tbls
from charon_tpu.core.eth2data import (
    Attestation,
    AttestationDuty,
    ParSignedData,
    Proposal,
    SignedData,
)
from charon_tpu.core.types import Duty, DutyType, PubKey, pubkey_to_bytes
from charon_tpu.eth2util.signing import ForkInfo


class VapiError(Exception):
    pass


class PreGenesisError(VapiError):
    """A registration whose timestamp lies before genesis."""


@dataclass
class ValidatorAPI:
    """share_idx: this node's 1-based share index; pubshares maps group
    pubkey -> this node's compressed pubshare bytes."""

    share_idx: int
    pubshares: dict[PubKey, bytes]
    fork: ForkInfo
    slots_per_epoch: int = 32
    # optional core.cryptoplane.SlotCoalescer: partial-sig pubshare checks
    # from concurrent VC submissions merge into one sharded device program
    plane: object | None = None
    tracer: object | None = None  # app/tracer.Tracer; None = process-global
    # core/parsigex.WaveRoster, the ONE the node's ParSigEx verifier
    # holds: the VC's set of a duty is this node's set of the wave the
    # plane verifies for it (the peers' come through ParSigEx), which a
    # submission tells the plane, with the senders the roster awaits, so
    # that the coalescing window closes when the wave is whole; None =
    # no hint (core/cryptoplane)
    roster: object | None = None
    # core/deadline.SlotClock: a builder registration's duty is the slot
    # of its timestamp; None = the router's (core/vapi_http hands a
    # clockless ValidatorAPI its own)
    clock: object | None = None

    def __post_init__(self) -> None:
        self._subs: list = []
        self._await_attestation = None
        self._await_proposal = None
        self._await_agg_att = None
        self._await_contrib = None
        self._await_sync_msg = None
        self._pubkey_by_att = None
        self._duty_defs = None
        self._await_aggregated = None

    # -- wiring ------------------------------------------------------------

    def subscribe(self, sub) -> None:
        self._subs.append(sub)

    def register_await_attestation(self, fn) -> None:
        self._await_attestation = fn

    def register_await_proposal(self, fn) -> None:
        self._await_proposal = fn

    def register_await_aggregated_attestation(self, fn) -> None:
        self._await_agg_att = fn

    def register_await_sync_contribution(self, fn) -> None:
        self._await_contrib = fn

    def register_await_sync_message(self, fn) -> None:
        self._await_sync_msg = fn

    def register_pubkey_by_attestation(self, fn) -> None:
        self._pubkey_by_att = fn

    def register_get_duty_definition(self, fn) -> None:
        self._duty_defs = fn

    def register_await_aggregated(self, fn) -> None:
        """AggSigDB await — serves aggregated selection proofs back to the
        VC (ref: validatorapi.go:724 AggregateBeaconCommitteeSelections
        returns combined selections, not partials)."""
        self._await_aggregated = fn

    # -- queries (VC pulls duty data; blocking until consensus) ------------

    async def attestation_data(self, slot: int, committee_index: int):
        """GET /eth/v1/validator/attestation_data analogue
        (ref: validatorapi.go:261 via dutydb.AwaitAttestation)."""
        duty = Duty(slot, DutyType.ATTESTER)
        defs = self._duty_defs(duty) if self._duty_defs else {}
        for pubkey, d in defs.items():
            if d.committee_index == committee_index:
                att_duty = await self._await_attestation(slot, pubkey)
                return att_duty.data
        raise VapiError(f"no attester duty for slot {slot} committee {committee_index}")

    async def proposal(self, slot: int, pubkey: PubKey) -> Proposal:
        return await self._await_proposal(slot, pubkey)

    # -- submissions (VC pushes partial signatures) ------------------------

    async def submit_attestations(self, atts: Sequence[Attestation]) -> None:
        """POST /eth/v1/beacon/pool/attestations analogue
        (ref: validatorapi.go:274 SubmitAttestations)."""
        if not atts:
            return

        def entries():
            for att in atts:
                slot = att.data.slot
                pubkey = self._pubkey_by_att(slot, att.data.hash_tree_root())
                if pubkey is None:
                    raise VapiError("unknown attestation (no DutyDB entry)")
                yield (
                    Duty(slot, DutyType.ATTESTER),
                    pubkey,
                    SignedData("attestation", att, att.signature),
                )

        await self._submit(Duty(atts[0].data.slot, DutyType.ATTESTER), entries())

    async def submit_proposal(self, pubkey: PubKey, proposal: Proposal, signature: bytes) -> None:
        signed = SignedData("block", proposal, signature)
        duty = Duty(proposal.slot, DutyType.PROPOSER)
        await self._submit(duty, [(duty, pubkey, signed)])

    async def submit_randao(self, slot: int, pubkey: PubKey, signature: bytes) -> None:
        """Randao reveals arrive with proposal requests
        (ref: validatorapi.go:335 Proposal flow)."""
        epoch = slot // self.slots_per_epoch
        signed = SignedData("randao", epoch, signature)
        duty = Duty(slot, DutyType.RANDAO)
        await self._submit(duty, [(duty, pubkey, signed)])

    async def submit_selection_proof(self, slot: int, pubkey: PubKey, signature: bytes) -> None:
        """Beacon-committee selection partials
        (ref: validatorapi.go:724 AggregateBeaconCommitteeSelections)."""
        signed = SignedData("selection_proof", slot, signature)
        duty = Duty(slot, DutyType.PREPARE_AGGREGATOR)
        await self._submit(duty, [(duty, pubkey, signed)])

    async def aggregate_attestation(self, slot: int, att_data_root: bytes):
        """Blocking fetch of the cluster-agreed aggregate."""
        return await self._await_agg_att(slot, att_data_root)

    async def submit_aggregate_and_proof(self, pubkey: PubKey, agg, signature: bytes) -> None:
        signed = SignedData("aggregate_and_proof", agg, signature)
        duty = Duty(agg.aggregate.data.slot, DutyType.AGGREGATOR)
        await self._submit(duty, [(duty, pubkey, signed)])

    async def aggregate_selection(self, slot: int, pubkey: PubKey):
        """Blocking fetch of the threshold-aggregated beacon-committee
        selection proof (ref: validatorapi.go:724 returns the combined
        proof after cluster-wide aggregation)."""
        duty = Duty(slot, DutyType.PREPARE_AGGREGATOR)
        return await self._await_aggregated(duty, pubkey)

    async def submit_sync_selection(
        self, slot: int, subcommittee_index: int, pubkey: PubKey, signature: bytes
    ) -> None:
        """Sync-committee selection partials
        (ref: validatorapi.go AggregateSyncCommitteeSelections)."""
        from charon_tpu.core.eth2data import SyncSelectionData

        payload = SyncSelectionData(slot, subcommittee_index)
        signed = SignedData("sync_selection", payload, signature)
        duty = Duty(slot, DutyType.PREPARE_SYNC_CONTRIBUTION)
        await self._submit(duty, [(duty, pubkey, signed)])

    async def sync_selection_aggregate(self, slot: int, pubkey: PubKey):
        duty = Duty(slot, DutyType.PREPARE_SYNC_CONTRIBUTION)
        return await self._await_aggregated(duty, pubkey)

    async def sync_contribution(
        self, slot: int, subcommittee_index: int, beacon_block_root: bytes
    ):
        """Blocking fetch of the cluster-agreed sync contribution."""
        return await self._await_contrib(
            slot, subcommittee_index, beacon_block_root
        )

    async def submit_contribution_and_proof(
        self, pubkey: PubKey, cap, signature: bytes
    ) -> None:
        signed = SignedData("contribution_and_proof", cap, signature)
        slot = cap.contribution.slot
        duty = Duty(slot, DutyType.SYNC_CONTRIBUTION)
        await self._submit(duty, [(duty, pubkey, signed)])

    async def sync_message_duty(self, slot: int, pubkey: PubKey):
        return await self._await_sync_msg(slot, pubkey)

    async def submit_sync_messages(self, msgs: Sequence[tuple[PubKey, object]]) -> None:
        """POST /eth/v1/beacon/pool/sync_committees analogue
        (ref: validatorapi.go SubmitSyncCommitteeMessages): the
        request's (pubkey, message) pairs are ONE set, as a request's
        attestations are — one verify job under the wave key the peers'
        sets carry, one `vapi.submit` span whose `count` is the
        request's size; one bad partial refuses the request whole."""
        if not msgs:
            return
        await self._submit(
            Duty(msgs[0][1].slot, DutyType.SYNC_MESSAGE),
            (
                (
                    Duty(msg.slot, DutyType.SYNC_MESSAGE),
                    pubkey,
                    SignedData("sync_message", msg, msg.signature),
                )
                for pubkey, msg in msgs
            ),
        )

    async def submit_exit(self, pubkey: PubKey, exit_msg, signature: bytes) -> None:
        """Voluntary exit partial (ref: exit flow, validatorapi exit
        endpoints + cmd/exit_sign.go)."""
        signed = SignedData("exit", exit_msg, signature)
        slot = exit_msg.epoch * self.slots_per_epoch
        duty = Duty(slot, DutyType.EXIT)
        await self._submit(duty, [(duty, pubkey, signed)])

    def registration_slot(self, reg) -> int:
        """The slot a builder registration's duty lies under: the slot
        of its timestamp (ref: validatorapi.go slotFromTimestamp), the
        one every operator's VC and so every peer's partial names. A
        timestamp before genesis names no slot — upstream fails the
        request ("registration timestamp before genesis"); filed under
        slot 0 it would meet no peer's partial and outlive no deadline."""
        if self.clock is None:
            raise VapiError("a registration's slot needs the slot clock")
        if reg.timestamp < self.clock.genesis_time:
            raise PreGenesisError("registration timestamp before genesis")
        return self.clock.slot_at(reg.timestamp)

    async def submit_registrations(self, items, slot: int | None = None) -> None:
        """POST /eth/v1/validator/register_validator analogue (ref:
        validatorapi.go SubmitValidatorRegistrations): `items` are the
        (pubkey, ValidatorRegistration, signature) of ONE request and,
        as a request's attestations or sync messages, ONE set a duty
        slot — one pubshare batch, one verify job under the wave key the
        peers' sets carry, one `vapi.submit` span whose `count` is the
        request's size; one bad partial refuses the request whole.
        `slot`: None = each registration's own (`registration_slot`)."""
        items = list(items)
        if not items:
            return
        entries = [
            (
                Duty(
                    self.registration_slot(reg) if slot is None else slot,
                    DutyType.BUILDER_REGISTRATION,
                ),
                pubkey,
                SignedData("registration", reg, signature),
            )
            for pubkey, reg, signature in items
        ]
        await self._submit(entries[0][0], entries)

    async def submit_registration(
        self, pubkey: PubKey, reg, signature: bytes, slot: int | None = None
    ) -> None:
        """A request of one registration."""
        await self.submit_registrations([(pubkey, reg, signature)], slot=slot)

    # -- helpers -----------------------------------------------------------

    async def _submit(self, duty: Duty, entries) -> None:
        """Where every submit_* ends: `entries` yields (duty, pubkey,
        signed) — lazily, so a submitter's own look-ups are inside the
        span. All signatures of the request are checked against this
        node's pubshares in ONE batch; then each duty's set goes to the
        subscribers (ParSigDB). Span `vapi.submit`: request parsed ->
        the last `parsigdb.store_internal` returned, in `duty`'s trace."""
        from charon_tpu.app.tracer import span  # lazy: core !-> app

        with span(
            "vapi.submit",
            duty=duty,
            tracer=self.tracer,
            duty_type=str(duty.type),
            count=0,
            rejected=0,
        ) as s:
            metas = list(entries)
            s.attrs["count"] = len(metas)
            signed_sets: dict[Duty, dict[PubKey, ParSignedData]] = {}
            for d, pk, signed in metas:
                signed_sets.setdefault(d, {})[pk] = ParSignedData(
                    data=signed, share_idx=self.share_idx
                )
            ok = await self._check_batch(
                [self._verify_item(pk, signed, d.slot) for d, pk, signed in metas],
                sets=[(d, frozenset(ss)) for d, ss in signed_sets.items()],
            )
            if not all(ok):
                s.attrs["rejected"] = sum(1 for lane in ok if not lane)
                raise VapiError(
                    "partial signature failed pubshare verification"
                )
            for d, signed_set in signed_sets.items():
                for sub in self._subs:
                    await sub(d, signed_set)

    def _verify_item(self, pubkey: PubKey, signed: SignedData, slot: int):
        pubshare = self.pubshares.get(pubkey)
        if pubshare is None:
            raise VapiError(f"unknown validator {pubkey}")
        root = signed.signing_root(self.fork, slot // self.slots_per_epoch)
        return (pubshare, root, signed.signature)

    async def _check_batch(self, items, sets=()) -> list[bool]:
        """Per-lane verdicts of partial signatures against pubshares — batched
        (ref: validatorapi.go:1213 one herumi call per signature). With a
        crypto plane installed, concurrent submissions coalesce into one
        sharded device program. `sets`: (duty, its validators in this
        request) per duty the request spans — for each, this batch is
        this node's set of that wave (the peers' come through ParSigEx
        under the same key)."""
        if self.plane is not None:
            import asyncio

            from charon_tpu.core.cryptosvc import PlaneOverloadError

            kwargs = {}
            if self.roster is not None and getattr(
                self.plane, "wave_hints", False
            ):
                kwargs["wave"] = tuple(
                    (key, self.roster.hint(key[0], self.share_idx))
                    for key in sets
                )
            try:
                ok = await self.plane.verify(items, **kwargs)
            except PlaneOverloadError:
                # admission shed (core/cryptosvc backpressure): this
                # VC's submission verifies on the host tbls rung, off
                # the event loop (host BLS would stall it for seconds)
                ok = await asyncio.get_running_loop().run_in_executor(
                    None, tbls.verify_batch, items
                )
        else:
            # plane-less rung (simnet/unit wiring + the no-accelerator
            # floor): deliberately INLINE — an executor hop here GIL-
            # convoys the busy loop and reorders duty timing (measured
            # 7-17x e2e slowdown); production wires the plane, whose
            # path above is truly async
            ok = tbls.verify_batch(items)  # lint: allow(event-loop-blocking)
        return ok
