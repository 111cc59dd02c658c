# -*- coding: utf-8 -*-
"""
What a trace chose, told to whoever asked: the one mechanism behind the
package's ``*_traces()`` context managers (``decode_impl_traces``,
``flash_bwd_traces``, ``flash_block_traces``, ``expert_route_traces``,
``remat_traces``, ``delta_step_traces``, ``head_loss_traces``).

A module that picks a form at trace time (kernel or XLA, fused or split,
which names a remat keeps) holds one :class:`TraceSinks` and calls
``note(record)`` where it decides; a smoke run, a benchmark driver or a
test opens a block around the compile and reads the records, so it
asserts the path the program holds instead of trusting it. A record is
kept only by the blocks open when it is noted.
"""

import contextlib

__all__ = ['TraceSinks']


class TraceSinks:
    """The open blocks of one ``*_traces()`` name. Falsy while none is
    open, so a caller can skip building a record nobody reads."""

    def __init__(self):
        self._open = []

    @contextlib.contextmanager
    def open(self):
        """A block: yields the list that :meth:`note` appends to until
        the block ends (nested blocks each get every record)."""
        sink = []
        self._open.append(sink)
        try:
            yield sink
        finally:
            self._open[:] = [s for s in self._open if s is not sink]

    def note(self, record):
        for sink in self._open:
            sink.append(record)

    def __bool__(self):
        return bool(self._open)
