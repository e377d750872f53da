"""Program spans of the serving loop, kept in memory and in the profiler.

A `Spans` recorder belongs to one scheduler call.  Each span is a record
`{"name", "start", "end", "parent", "attrs"}`: times are seconds since the
scheduler's `t0` on `time.time()`, the clock of `stats["token_times"]`, and
`parent` is the index of the enclosing record (None at the top).  Records
are listed in the order they opened, so a parent comes before its children.
Each span is also a `jax.profiler.TraceAnnotation` of the same name and
attributes, so that a profiler session holds it on the clock of the device
planes; with no session that costs about a microsecond.
"""

from __future__ import annotations

import contextlib
import time

import jax


class Span:
    """An open span: `set` adds attributes known only once it has run,
    `drop` takes back a span that turned out to cover nothing.  The
    annotation of a dropped span stays in a profiler session that holds
    it."""

    def __init__(self, record: dict, annotation: jax.profiler.TraceAnnotation):
        self.record = record
        self._annotation = annotation
        self.dropped = False

    def set(self, **attrs) -> None:
        self.record["attrs"].update(attrs)
        self._annotation.set_metadata(**attrs)

    def drop(self) -> None:
        self.dropped = True


class Spans:
    """The records of one scheduler call, whose t0 is `t0`."""

    def __init__(self, t0: float):
        self.t0 = t0
        self.records: list[dict] = []
        self._open: list[int] = []

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        """A span called `name` over the `with` block; yields its `Span`."""
        idx = len(self.records)
        record = {"name": name, "start": None, "end": None,
                  "parent": self._open[-1] if self._open else None,
                  "attrs": dict(attrs)}
        self.records.append(record)
        self._open.append(idx)
        annotation = jax.profiler.TraceAnnotation(name, **attrs)
        handle = Span(record, annotation)
        try:
            with annotation:
                record["start"] = time.time() - self.t0
                try:
                    yield handle
                finally:
                    record["end"] = time.time() - self.t0
        finally:
            self._open.pop()
            if handle.dropped:
                # nothing opened inside a span that is taken back
                assert self.records[-1] is record, name
                self.records.pop()

    def rebase(self, t0: float) -> None:
        """Move the clock's zero to `t0`: the records made so far keep their
        times on `time.time()`, so those made before `t0` end before 0."""
        shift = t0 - self.t0
        for record in self.records:
            record["start"] -= shift
            record["end"] -= shift
        self.t0 = t0

    def last(self, name: str, since: int = 0) -> dict | None:
        """The newest closed record called `name` among records `since`
        onward."""
        for i in range(len(self.records) - 1, since - 1, -1):
            record = self.records[i]
            if record["name"] == name and record["end"] is not None:
                return record
        return None
