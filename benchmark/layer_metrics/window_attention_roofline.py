"""Kernels (ops/diff_attention.py, scope ``window_attention``): the eight
window layers' pool write, gather through the windowed table and
differential attention, as a share of their roofline over the traced window.
Needed: K and V of a row's live window (from its first query's window start
to its last token) read once a layer, the step's tokens written, and the two
softmax maps of every head pair over every causal pair inside the window;
the first form gathers the whole 35-block table of every row and forms it
again for the matrix unit, which counts as overhead. Moves tpot_p95_ms."""

from benchmark import family_rooflines as rooflines


def read(run):
    return rooflines.scope_share(run, "window_attention")
