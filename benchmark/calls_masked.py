"""How the masked round calls into the program: the adapters of
:mod:`benchmark.calls` for the calling conventions that round brings, named
by dotted path in its traffic file (``masked_engine_call``).

No adapter passes a backend or a seed: the program picks the rounds'
implementation from the devices it is compiled for, and draws the seeds from
the step's key.
"""

from __future__ import annotations


def masked_chunk_engine(masked_entry, entry, plan, masking):
    """``masked_entry(entry, plan, masking) -> fn(secrets, key) ->
    (accumulator, seeds, counts)`` over one chunk of rows, ``entry`` being
    the chunk entry underneath (``entry(secrets, key, plan) -> accumulator``)."""
    return masked_entry(entry, plan, masking)
