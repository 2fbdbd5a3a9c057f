"""The plain reference for a join over rate-limited, delayed links
(``join-links``). It imports nothing of the program and nothing of the
generator's link model: it holds the generator's link logs to the law a
serial link obeys, worked out again here from the configuration.

A link carries responses ONE at a time at ``rate`` bytes a second, half
a round trip behind the request and half a round trip before the
hand-over. So for the rows ``(request, due, handed_over, bytes)`` of one
link, in request order:

    leaves[i] = max(request[i] + rtt/2, leaves[i-1]) + bytes[i] / rate
    handed_over[i] >= leaves[i] + rtt/2            (late is allowed)

and from that, whatever the order of hand-overs, the bytes handed over
by any instant are at most ``rate x (instant - first request)`` plus one
block (the token law).
"""

from __future__ import annotations

EPS_S = 1e-6  # the clock's own grain, not a tolerance of the law


def under_floor(config: dict, rates: dict) -> set:
    """The peers whose link is slower than the pool's floor."""
    return {p for p, r in rates.items() if r < config["min_recv_rate_bps"]}


def link_ceiling_blocks_per_s(config: dict) -> float:
    """No join can apply blocks faster than every link at the sound
    rate could carry them."""
    return config["peers"] * config["link_rate_bps"] / config["block_wire_bytes"]


def check_log(rate_bps: float, rtt_s: float, rows: list) -> dict:
    """One link's log against the law. ``rows`` are ``(request, due,
    handed_over or None, bytes)``; a request that was taken back is
    never handed over, and still held the link for its bytes."""
    half = rtt_s / 2.0
    rows = sorted(rows, key=lambda r: r[0])
    leaves = 0.0
    early_bytes = 0
    before_rtt = 0
    handed = []
    for request, _due, handed_over, nbytes in rows:
        leaves = max(request + half, leaves) + nbytes / rate_bps
        if handed_over is None:
            continue
        handed.append((handed_over, nbytes))
        if handed_over < leaves + half - EPS_S:
            early_bytes += nbytes
        if handed_over - request < rtt_s - EPS_S:
            before_rtt += 1
    over = 0.0
    if handed:
        first = rows[0][0]
        one_block = max(n for _, n in handed)
        total = 0
        for at, nbytes in sorted(handed):
            total += nbytes
            over = max(over, total - rate_bps * (at - first) - one_block)
    return {
        "overrun_bytes": early_bytes + max(0.0, over),
        "before_rtt": before_rtt,
        "handed_over": len(handed),
        "bytes": sum(n for _, n in handed),
    }


def check_join(config: dict, rates: dict, logs: dict) -> dict:
    """Every link of one join: ``rates`` and ``logs`` by peer."""
    out = {"overrun_bytes": 0.0, "before_rtt": 0, "handed_over": 0}
    rtt_s = config["link_rtt_ms"] / 1e3
    for peer, rows in logs.items():
        got = check_log(rates[peer], rtt_s, rows)
        for k in out:
            out[k] += got[k]
    return out
