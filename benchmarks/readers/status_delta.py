"""Per-layer metrics from exact counts: the change of
`GenerationServer.status()` over the window (`ctx["driver"]
["status_delta"]`) and what warm-up reported (`ctx["driver"]["warm"]`)."""
from __future__ import annotations


def read(ctx, spec):
    d = ctx["driver"]
    key = spec["key"]
    if key == "compiled":
        return d["warm"]["compiled"]
    delta = d.get("status_delta")
    if not delta:
        return None
    dispatches = delta["steps"] + delta["admissions"]
    if key == "host_syncs_per_token":
        return delta["token_fetches"] / delta["tokens"] \
            if delta["tokens"] else None
    if key == "tokens_per_dispatch":
        return delta["tokens"] / dispatches if dispatches else None
    raise ValueError(f"status_delta: unknown key {key!r}")
