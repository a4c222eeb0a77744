"""The two ends of a decoder-only serve step that let the engine run one
step ahead of its own fetch (``serving.paged_lm.PagedLMGenerator``): where
a row's input token comes from, and at what length the next tokens go out.

A decode row's input is the token its lane emitted in the step before.
Where the host has not fetched that step yet, the token exists only on the
device, in the last step's ``next_ids``; the step reads it there.  So every
step program of a generator takes the last step's ids whole (``prev_ids``,
fed as the device array it is) and emits its own at the SAME length
(``n_lanes + prefill_slots``, whatever the number of chunks this variant
carries): one executable a variant, whichever variant ran before it."""

from __future__ import annotations

from ..fluid import layers

__all__ = ["fed_tokens", "emitted_ids"]


def fed_tokens(n_rows: int, n_ids: int):
    """The step's ``[n_rows]`` input tokens, from three feeds: ``tok``
    [n_rows] int64, what the host knows; ``prev_ids`` [n_ids], the last
    step's ``emitted_ids``; ``tok_src`` [n_rows] int32, for each row where
    to read: ``i`` (row ``i`` of ``tok``, the row's own) or ``n_rows + j``
    (the last step's id ``j``)."""
    def feed(name, n, dtype):
        return layers.data(name, [n], dtype, append_batch_size=False)

    tok, prev = feed("tok", n_rows, "int64"), feed("prev_ids", n_ids, "int64")
    return layers.gather(layers.concat([tok, prev], axis=0),
                         feed("tok_src", n_rows, "int32"))


def emitted_ids(next_ids, n_out: int, n_ids: int):
    """``next_ids`` [n_out] at the generator's one length ``n_ids``: zeros
    behind the rows this variant has."""
    if n_out == n_ids:
        return next_ids
    return layers.concat(
        [next_ids, layers.fill_constant([n_ids - n_out], "int32", 0)],
        axis=0)
