"""The scheduler's program spans, as the metrics read them.

`serve` keeps each span as a record `{"name", "start", "end", "parent",
"attrs"}` in `stats["spans"]`, in the order they opened, with times in
seconds since its t0: the window's clock.  A reader takes the records that
end inside the window.  A program that keeps no spans gives no records.
"""

from __future__ import annotations


def ending_inside(ctx, name: str) -> list[tuple[int, dict]]:
    """(index, record) of every span called `name` that ends inside the
    window."""
    return [(i, r) for i, r in enumerate(ctx.stats.get("spans") or ())
            if r["name"] == name and 0.0 <= r["end"] <= ctx.window.seconds]


def rounds(ctx) -> list[tuple[dict, dict]]:
    """(round, its token read-back) of every decode round that ends inside
    the window: each `serve.round` with its `serve.round.wait`."""
    spans = ctx.stats.get("spans") or ()
    wait = {r["parent"]: r for r in spans if r["name"] == "serve.round.wait"}
    return [(r, wait[i]) for i, r in ending_inside(ctx, "serve.round")]


def seconds(record: dict) -> float:
    return record["end"] - record["start"]
