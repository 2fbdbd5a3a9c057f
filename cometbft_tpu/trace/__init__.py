"""Always-on low-overhead tracing plane (docs/TRACE.md).

Per-node fixed-size ring-buffer tracers with a span API over the hot
planes (consensus step lifecycle, blocksync windows, crypto batch
verify, mempool, WAL fsync), Chrome trace-event / JSONL export
(Perfetto-loadable) and p50/p95/p99 summaries.

Two tracer scopes:

- **per-node** — built by node/inprocess.build_node when
  ``[instrumentation] trace_enabled`` (default on); carried on
  NodeParts.tracer and attached to the node's consensus state,
  mempool, WAL, blocksync reactor and switch.
- **process-wide** — ``global_tracer()``: the landing zone for
  planes shared across in-process nodes (the verify path: the commit
  seam, the verify scheduler, the kernel dispatch, the host chunk
  pool). On from import, like every node's own tracer, so the verify
  path is recorded where no node is built (a light server, an
  embedder driving the seam); ``build_node`` anchors its clock;
  worker subprocesses switch it off (crypto/parallel_verify), so the
  pickled chunk path stays no-op there.

Instrumented classes default ``self.tracer`` to the shared ``NOOP``
tracer, so call sites are unconditional and the disabled path is one
attribute check (tests/test_trace.py bounds it).
"""

from .bridge import SpanMetricsBridge
from .export import chrome_trace, read_jsonl, write_chrome, write_jsonl
from .summary import (
    format_summary,
    percentile,
    summarize,
    summarize_by_height,
)
from .timeline import (
    attribute_heights,
    attribution_key,
    format_waterfall,
    merge_events,
    rebase,
)
from .tracer import (
    NOOP,
    NOOP_SPAN,
    Tracer,
    current_ticket,
    ticket_scope,
)

__all__ = [
    "NOOP",
    "NOOP_SPAN",
    "SpanMetricsBridge",
    "Tracer",
    "attribute_heights",
    "attribution_key",
    "chrome_trace",
    "current_ticket",
    "enable_global",
    "format_summary",
    "format_waterfall",
    "global_tracer",
    "merge_events",
    "percentile",
    "read_jsonl",
    "rebase",
    "summarize",
    "summarize_by_height",
    "ticket_scope",
    "write_chrome",
    "write_jsonl",
]

# process-wide tracer for cross-node planes (the verify path). The
# ring has to hold a whole measured window of a ticket's spans (the
# benchmark's readers give nothing once it has dropped one). A
# device-routed ticket leaves 11 spans, a host-routed one of 150 lanes
# 19 (13 crypto.verify_chunk). The window that makes most tickets is
# the benchmark's val150.commit-live, one ticket a commit: 25 commits/s
# x 40 s + 64 of warm-up + 12 of the traced slice = 1,076 tickets;
# x 19 spans x 1.25 of room = 25,555 slots; the next power of two.
# (qa175.verify-only: ~800 tickets of 11, and twice that fit.)
_GLOBAL = Tracer(name="process", size=32768)


def global_tracer() -> Tracer:
    return _GLOBAL


def enable_global(enabled: bool = True) -> Tracer:
    """Flip the process-wide tracer; idempotent (called by every
    tracing-enabled node build, and with False by pool workers)."""
    _GLOBAL.enabled = enabled
    return _GLOBAL
