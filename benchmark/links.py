"""Readers for a cell whose joining node fetches over rate-limited,
delayed links (``val150.catchup-delayed``): what the pool's choices
among its peers cost, all moving ``catchup_rate``.

  link_utilisation          bytes the pool counted from the peers at or
                            above the floor, over what their links
                            could carry in the seconds between each
                            join's first request and its last block,
                            summed over the window's joins. The link
                            law keeps it at or under 100: a reading
                            above means the link model leaks, and is
                            never clamped
  head_of_line_wait_share   ``blocksync.window.fetch_wait`` spans that
                            began with blocks buffered (the loop waits
                            for the window's HEAD alone), over the
                            window's wall
  slow_peer_block_share     blocks the pool took from the peers under
                            the floor, over blocks applied (those a ban
                            dropped unapplied count too): 10% is an even
                            deal, ~1.4% the slow peer's share by rate
  slow_peer_ban_s           a join's start to the ``rate`` ban of its
                            slow peer (``blocksync.pool.ban``), mean
                            over the joins that banned it
  blocks_per_window         mean ``jobs`` of ``blocksync.window.verify_wait``:
                            how ragged the trickle makes the windows

A record that lacks what a reader reads (another generator's, a program
whose pool counts nothing or records no such span or arg) gives None,
and the harness leaves the metric out of the line.
"""

from __future__ import annotations

from benchmark.probes import say


def _joins(rec: dict):
    links = rec.get("links")
    return links["joins"] if links else None


def link_utilisation(rec: dict):
    joins = _joins(rec)
    if not joins or any(j["pool"] is None for j in joins):
        return None
    got = sum(
        s["bytes"]
        for j in joins for p, s in j["pool"]["peers"].items() if p not in j["slow"]
    )
    could = rec["links"]["sound_capacity_bps"] * sum(j["fetch_s"] for j in joins)
    return 100.0 * got / could if could else None


def head_of_line_wait_share(rec: dict):
    links = rec.get("links")
    if not links or links["head_wait_s"] is None or not rec.get("window_s"):
        return None
    return 100.0 * links["head_wait_s"] / rec["window_s"]


def slow_peer_block_share(rec: dict):
    joins = _joins(rec)
    if not joins or any(j["pool"] is None for j in joins):
        return None
    applied = sum(j["blocks_applied"] for j in joins)
    slow = sum(j["pool"]["peers"][p]["blocks"] for j in joins for p in j["slow"])
    return 100.0 * slow / applied if applied else None


def slow_peer_ban_s(rec: dict):
    joins = _joins(rec)
    if not joins:
        return None
    waits = [j["rate_ban_s"] for j in joins if j["rate_ban_s"] is not None]
    say(
        f"links: {len(waits)} of {len(joins)} joins of the window banned their "
        f"slow peer for its rate; {len(joins) - len(waits)} never did"
    )
    return sum(waits) / len(waits) if waits else None


def blocks_per_window(rec: dict):
    if not rec.get("links"):
        return None
    jobs = [
        s["jobs"] for s in rec.get("spans", [])
        if s["name"] == "blocksync.window.verify_wait" and s["jobs"] is not None
    ]
    return sum(jobs) / len(jobs) if jobs else None
