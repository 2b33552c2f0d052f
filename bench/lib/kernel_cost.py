"""Bytes the rank sweep needs, from shapes alone.

One sweep scores every node: it reads the node streams of Eq. 1-2 (power
at the current occupancy, PUE, intensity now and forecast, efficiency,
schedule weight; with the marginal-CFP term also full-load power, free
chips and installed chips), float32 each, and writes one float32 score
per node.  The per-tile candidate lists a kernel may also write are left
out: a sweep that writes less is not scored down for it.
"""

BASE_STREAMS = 6
MARGINAL_STREAMS = 3
F32 = 4


def sweep_bytes(n_nodes: int, lanes: int = 1, marginal: bool = False) -> int:
    streams = BASE_STREAMS + (MARGINAL_STREAMS if marginal else 0)
    return lanes * n_nodes * F32 * (streams + 1)
