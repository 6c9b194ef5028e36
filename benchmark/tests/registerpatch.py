"""Patches for `run.Rehearsal`, beside planepatch.py: what the PARENT
program lacks to complete a validator client's builder registrations, one
patch a gap, each named for the change in `charon_tpu/` it stands for and
no larger than that change. tests/rehearse_register.py carries them all by
default; `--unpatched` and `--without <name>` put on record what each gap
costs. They are this PR's finding (PERF.md §7) and the next issue's
tentpole: once `charon_tpu/` has the change, its patch goes.

- `registration_slot_from_timestamp` — `core/vapi_http._register_validator`
  hands `ValidatorAPI.submit_registration` no slot, so every registration of
  a VC is filed under `Duty(0, BUILDER_REGISTRATION)` whatever it says,
  while the peers' partials of the same registration travel under the slot
  of its timestamp (upstream: `core/validatorapi` takes the duty's slot from
  the registration's timestamp). The node's own VC's partials then never
  meet its peers': a cluster with a spare operator completes on the peers'
  t alone and wastes its own set, a cluster at bare quorum completes none;
  and slot 0's deadline (`SlotClock.duty_deadline`, 30 s) has passed long
  before a chip run's window opens. The change: the router passes
  `slot=self.clock.slot_at(reg.timestamp)`.
- `registrations_one_request_one_set` — the same handler awaits
  `submit_registration` once a registration and `ValidatorAPI` makes each a
  set of one: behind a crypto plane each waits out a coalescing window of its
  own, one after the other (its wave key — the duty and ONE validator — is
  no peer's, so no window closes `complete`), B x the window a request. The
  change: `ValidatorAPI.submit_registrations(items)`, one `_submit` of the
  whole request under each registration's own duty, as
  `submit_sync_messages` is since PR 39, and the router calling it once."""

from __future__ import annotations

PATH = "/eth/v1/validator/register_validator"


def registration_slot_from_timestamp(server) -> None:
    vapi, clock = server.node.vapi, server.node.vapi_router.clock
    inner = vapi.submit_registration
    vapi.registration_slot = lambda reg: clock.slot_at(reg.timestamp)

    async def submit_registration(pubkey, reg, signature, slot=0):
        await inner(pubkey, reg, signature, slot=vapi.registration_slot(reg))

    vapi.submit_registration = submit_registration


def registrations_one_request_one_set(server) -> None:
    from aiohttp import web

    from charon_tpu.core import vapi_http
    from charon_tpu.core.eth2data import SignedData
    from charon_tpu.core.types import Duty, DutyType
    from charon_tpu.core.validatorapi import VapiError
    from charon_tpu.eth2util.registration import ValidatorRegistration

    vapi, router = server.node.vapi, server.node.vapi_router

    async def submit_registrations(items) -> None:
        """items: [(pubkey, ValidatorRegistration, signature)] of ONE request."""
        slot_of = getattr(vapi, "registration_slot", lambda reg: 0)
        entries = [(Duty(slot_of(reg), DutyType.BUILDER_REGISTRATION), pubkey,
                    SignedData("registration", reg, signature))
                   for pubkey, reg, signature in items]
        if entries:
            await vapi._submit(entries[0][0], entries)

    vapi.submit_registrations = submit_registrations

    async def register_validator(request):
        try:
            items = [(ValidatorRegistration(
                fee_recipient=vapi_http._unhex(r["message"]["fee_recipient"]),
                gas_limit=int(r["message"]["gas_limit"]),
                timestamp=int(r["message"]["timestamp"]),
                pubkey=vapi_http._unhex(r["message"]["pubkey"])),
                vapi_http._unhex(r["signature"])) for r in await request.json()]
        except (KeyError, ValueError, TypeError) as e:
            return vapi_http._err(400, f"malformed registration: {e}")
        try:
            await vapi.submit_registrations(
                [(router._resolve_pubkey("0x" + reg.pubkey.hex()), reg, sig)
                 for reg, sig in items])
        except VapiError as e:
            return vapi_http._err(400, str(e))
        return web.Response(status=200)

    for route in router.app.router.routes():
        if route.resource.canonical == PATH and route.method == "POST":
            route._handler = register_validator
            return
    raise RuntimeError(f"the router has no POST {PATH}")


PATCHES = {f.__name__: f for f in (registration_slot_from_timestamp,
                                   registrations_one_request_one_set)}
