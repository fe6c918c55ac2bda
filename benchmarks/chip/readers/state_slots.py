"""/worker/stats polled at 1 Hz: the state slots a hybrid model's live
sequences hold (one each, from a sequence's first chunk to its last token:
engine/kv_cache.py) over the slots there are, as a percentage, summed over
the window's snapshots (`memory.state_slots`: {"held", "total", "bytes"}).
A program without state slots (no `memory.state_slots`) reads 0: it holds
none."""


def read(ctx, args):
    held = total = 0.0
    for _, stats in ctx.snapshots:
        s = (stats.get("memory") or {}).get("state_slots") or {}
        held += float(s.get("held", 0))
        total += float(s.get("total", 0))
    return 100.0 * held / total if total > 0 else 0.0
