"""The comparison that decides `correct`, run once the window has closed,
the device's memory peak is read and the node is torn down. For every duty
of the window: the attestation the node's beacon received carries the data
the traffic plan gave the cluster; its aggregate is, byte for byte, the
plain reference's signature by the group secret on the signing root the
reference computes from those raw fields; the duty's public key is the
reference's public key of that secret. And: every duty broadcast exactly
once, the forged partial set rejected, no degradation rung taken, nothing
compiled inside the window. Each number has its own limit; all are exact,
so every limit is 0."""

from __future__ import annotations

from benchmark import reference


def compare(run, cluster, plan, chain: tuple[bytes, bytes], degradation: dict,
            rejected: int, expected_forged: int, compiles_in_window: int) -> dict:
    """`chain` is (fork version, genesis validators root): the chain's
    parameters as the cluster's lock file states them."""
    missing = duplicated = data_differ = roots_differ = differ = keys_differ = 0
    for d in run.duties:
        if d.done is None or d.signature is None:
            missing += 1
            continue
        if d.broadcasts != 1:
            duplicated += 1
        fields = plan.attestation_fields(d.slot, plan.members(d.slot).index(d.vidx))
        if d.data != fields:
            data_differ += 1
        root = reference.attestation_signing_root(fields, *chain)
        if d.root != root:  # what the VC and the peers signed: the program's SSZ
            roots_differ += 1
        secret = cluster.group_secrets[d.pubkey]
        if d.signature != reference.sign(secret, root):
            differ += 1
        if bytes.fromhex(d.pubkey[2:]) != reference.secret_to_public_key(secret):
            keys_differ += 1
    burned = sorted(k for k, v in degradation.items() if v)
    checks = {
        "duties_missing": {"value": missing, "limit": 0},
        "duties_duplicated": {"value": duplicated, "limit": 0},
        "attestation_data_differ": {"value": data_differ, "limit": 0},
        "signing_roots_differ": {"value": roots_differ, "limit": 0},
        "aggregates_differ": {"value": differ, "limit": 0},
        "group_keys_differ": {"value": keys_differ, "limit": 0},
        "forged_sets_not_rejected": {"value": abs(expected_forged - rejected), "limit": 0},
        "degradation_events": {"value": sum(int(v) for v in degradation.values()),
                               "limit": 0},
        "compiles_in_window": {"value": compiles_in_window, "limit": 0},
    }
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
