"""Bytes the rank sweep needs, from shapes alone.

One sweep scores every node: it reads the node streams of Eq. 1-2 (power
at the current occupancy, PUE, intensity now and forecast, efficiency,
schedule weight; with the marginal-CFP term also full-load power, free
chips and installed chips), float32 each, and writes one float32 score
per node.  A room-aware sweep masks nodes without room for the call's
smallest arrival: without the marginal term it reads the room as one
more stream, with it the free-chips stream is the room.  The per-tile
candidate lists a kernel may also write are left out: a sweep that
writes less is not scored down for it.

On a device mesh one launch on one device sweeps its own block of lanes
and nodes: give the block's shape (the driver's ``sweep_shape`` does).
"""

BASE_STREAMS = 6
MARGINAL_STREAMS = 3
F32 = 4


def sweep_bytes(n_nodes: int, lanes: int = 1, marginal: bool = False,
                room: bool = False) -> int:
    streams = BASE_STREAMS + (MARGINAL_STREAMS if marginal else int(room))
    return lanes * n_nodes * F32 * (streams + 1)
