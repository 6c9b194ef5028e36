"""The comparison that decides `correct`, run once the window has closed,
the device's memory peak is read and the node is torn down. For every duty
of the window, of whatever kind: the object the node's beacon received
carries the data the traffic plan gave the cluster (the kind's module says
what that is, by the plain reference, and under which of the names below
a difference counts); its aggregate is, byte for byte, the plain
reference's signature by the group secret on the signing root the
reference computes from those raw fields; the duty's public key is the
reference's public key of that secret. And: every duty broadcast exactly
once, the forged partial set rejected, no degradation rung taken, nothing
compiled inside the window. Each number has its own limit; all are exact,
so every limit is 0."""

from __future__ import annotations

from benchmark import reference

# the numbers counted duty by duty, in the order they are printed; a kind's
# DATA_CHECK names the one its data comparison counts under
PER_DUTY = ("duties_missing", "duties_duplicated", "attestation_data_differ",
            "signing_roots_differ", "aggregates_differ", "group_keys_differ")


def compare(run, cluster, plan, chain: tuple[bytes, bytes], degradation: dict,
            rejected: int, expected_forged: int, compiles_in_window: int) -> dict:
    """`chain` is (fork version, genesis validators root): the chain's
    parameters as the cluster's lock file states them."""
    count = dict.fromkeys(PER_DUTY, 0)
    kinds = {kind.NAME: kind for kind in plan.kinds}
    for d in run.duties:
        if d.done is None or d.signature is None:
            count["duties_missing"] += 1
            continue
        if d.broadcasts != 1:
            count["duties_duplicated"] += 1
        fields, root = kinds[d.kind].expected(plan, d, chain)
        if d.data != fields:
            count[kinds[d.kind].DATA_CHECK] += 1
        if d.root != root:  # what the VC and the peers signed: the program's SSZ
            count["signing_roots_differ"] += 1
        secret = cluster.group_secrets[d.pubkey]
        if d.signature != reference.sign(secret, root):
            count["aggregates_differ"] += 1
        if bytes.fromhex(d.pubkey[2:]) != reference.secret_to_public_key(secret):
            count["group_keys_differ"] += 1
    burned = sorted(k for k, v in degradation.items() if v)
    checks = {name: {"value": value, "limit": 0} for name, value in count.items()}
    checks.update({
        "forged_sets_not_rejected": {"value": abs(expected_forged - rejected), "limit": 0},
        "degradation_events": {"value": sum(int(v) for v in degradation.values()),
                               "limit": 0},
        "compiles_in_window": {"value": compiles_in_window, "limit": 0},
    })
    if burned:
        checks["degradation_events"]["which"] = burned
    return checks


def verdict(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def report(checks: dict) -> str:
    lines = ["correctness: number compared, its value, its limit"]
    for name, c in checks.items():
        ok = "ok" if c["value"] <= c["limit"] else "FAILED"
        extra = f" {c['which']}" if "which" in c else ""
        lines.append(f"  {name} {c['value']} limit {c['limit']} {ok}{extra}")
    lines.append(f"correct: {verdict(checks)}")
    return "\n".join(lines)
