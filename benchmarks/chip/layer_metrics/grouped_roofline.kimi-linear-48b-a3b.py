"""The grouped product's share of its roofline in the delta-rule tower's
gated expert layers: the least time of the six products an expert layer
needs (``costs_latent_seq.grouped_least_seconds``: two forward and four
backward, of rows x 2304 x 2048 and rows x 1024 x 2304 at published
widths, once each whatever is recomputed), at the rows the placement
counted as routed to the held experts of each of the four expert layers
(its counters ``routed_rows_layer_<i>`` over ``routed_batches``: a probe
of the last batches of the window, so numerator and traced time follow
the same routing), over the device time a step of the trace groups
``gmm`` and ``tgmm``, the jitted functions of JAX's megablox that hold
the Pallas calls. It reads low by construction: at 8 held of 256 routed
and 8 a token a layer sees about 2048 rows a step, 1/32 of its
deployment's, so the need is about half a millisecond a layer, set by
the matrices' bytes and not by the rows, against a loop that sorts 65 536
pairs. What it guards is the shared dispatch at a router four times as
wide as the other gated cells'. A step that runs none of the groups, or
a placement that counts no routed rows, has nothing to read here."""

import costs_kda_seq
from weights_kda_seq import sizes_of

GROUPS = ("gmm", "tgmm")


def read(r):
    if r.trace is None or r.peaks is None or not r.trace["steps"]:
        return None
    seconds = sum(s for group, s in r.trace["ops"] if group in GROUPS)
    probed = r.counters.get("routed_batches")
    if seconds <= 0 or not probed:
        return None
    sz = sizes_of(r.config)
    rows = [r.counters[f"routed_rows_layer_{i}"] / probed
            for i in range(sz["pattern"].count("E"))]
    least = costs_kda_seq.grouped_least_seconds(sz, rows, r.peaks)
    return 100.0 * least * r.trace["steps"] / seconds
