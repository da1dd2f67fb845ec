"""What a correct pull looks like, from the block list and the donor's log
alone: standard library, imports nothing of the program (the consensus side
of ``correct`` stays ``reference.py``'s, through ``compare.py``).

The donor holds the window's blocks by position and logs, in order, every
request that reached it (the chain-info request, the locator, each
``requestantipast`` with the hash it named) and every chunk it answered with
(the positions the chunk stands for, ``first`` to ``last``, and the
positions whose blocks it really sent).  A chunk is *acknowledged* when the
syncee asked for the next one after it, or when it was the list's last: by
then the syncee has said it took the chunk in, so every block the chunk
stands for must be held with a final status — an acknowledged chunk is read
back.

Four counts, each 0 in a sound run:

- ``ibd_blocks_missing``: positions of acknowledged chunks the syncee does
  not hold with a final status;
- ``ibd_blocks_unsent_held``: window blocks the syncee holds that no chunk
  carried;
- ``ibd_rerequests``: requests that ask for a position an earlier request
  had asked for;
- ``ibd_bad_continuations``: a locator whose highest hash is not the ramp's
  sink, or that is not the first request for blocks; a ``requestantipast``
  that does not follow a chunk, or names another hash than that chunk's
  last block.
"""

from __future__ import annotations

LOCATOR = "ibdblocklocator"
ANTIPAST = "requestantipast"


def check(window_hashes: list, ramp_sink: bytes, log: list, held: dict, final: tuple) -> dict:
    """``window_hashes``: the donor's blocks by position; ``ramp_sink``: the
    sink of what the syncee held before; ``held``: hash -> status of every
    window block the syncee's store has; ``final``: the statuses a block that
    was taken in is left with."""
    position = {h: i for i, h in enumerate(window_hashes)}
    missing = rerequests = bad = 0
    asked: set = set()  # positions a request asked to start from
    sent: set = set()
    before = None  # the event before this one, chain-info request aside
    events = [e for e in log if e["event"] == "chunk" or e.get("msg") in (LOCATOR, ANTIPAST)]
    for n, e in enumerate(events):
        if e["event"] == "chunk":
            sent.update(e["sent"])
            acknowledged = e["first"] is not None and (e["done"] or any(x["event"] == "request" for x in events[n + 1:]))
            if acknowledged:
                missing += sum(1 for i in range(e["first"], e["last"] + 1) if held.get(window_hashes[i]) not in final)
        else:
            if e["msg"] == LOCATOR:
                start = 0
                if before is not None or not e["locator"] or e["locator"][0] != ramp_sink:
                    bad += 1
            else:
                start = position.get(e["low"], -2) + 1
                if before is None or before["event"] != "chunk" or before["last"] is None or e["low"] != window_hashes[before["last"]]:
                    bad += 1
            if start in asked:
                rerequests += 1
            asked.add(start)
        before = e
    unsent_held = sum(1 for h in held if position.get(h) not in sent)
    return {"ibd_blocks_missing": missing, "ibd_blocks_unsent_held": unsent_held,
            "ibd_rerequests": rerequests, "ibd_bad_continuations": bad}
