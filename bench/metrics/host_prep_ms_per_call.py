"""Host entry: the traced calls' time in the program's host spans
``fabric.prepare``, ``.init``, ``.run`` and ``.split`` (normalisation,
the init and run dispatches, result assembly), with ``fabric.fetch``
(the wait on the chip) left out, in ms per call
(``bench.phase_reduce``)."""
from bench import phase_reduce


def read(ctx):
    red = phase_reduce.load(ctx)
    if red is None:
        return None
    spans = red["spans"]
    calls = sum(n.startswith("bench.call.") for n, _, _ in spans)
    prep = [t - s for n, s, t in spans if n in phase_reduce.PREP_SPANS]
    if not calls or not prep:
        return None
    return sum(prep) / calls / 1e6
