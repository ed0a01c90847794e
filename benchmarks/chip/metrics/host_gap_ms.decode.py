"""Mean over pairs of consecutive traced engine steps that both decode of
the host time from the end of the first's token read-back (``readback``
span) to the end of the second's first program dispatch
(``prefill_chunk`` or ``decode_step``), ms (engine spans in the trace);
None without the spans."""

from benchmarks.chip import spans


def read(ctx):
    gaps = spans.host_gaps(getattr(ctx, "spans", None) or [])
    if not gaps:
        return None
    return 1000.0 * sum(gaps) / len(gaps)
