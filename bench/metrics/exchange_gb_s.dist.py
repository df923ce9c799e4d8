"""Rate of the halo exchange on a chip, GB/s (1e9 bytes): the program's
counter `gas/dist/exchange_bytes` (the remote halo rows an epoch over
every hidden layer and chip, as stored) times the window's epochs, over
the seconds in which each chip had a permute in flight, summed over
chips. A permute is asynchronous: it is in flight from the start of its
`collective-permute-start` op to the end of its `collective-permute-done`
op, most of that time under other ops, so its own ops' device time is
not its transfer time. Starts and dones are counted, not paired, so the
in-flight time needs no matching of the two. Nothing to read without the
program's recording."""

START, DONE, SYNC = ("collective-permute-start", "collective-permute-done",
                     "collective-permute")


def in_flight_ns(events) -> int:
    """Nanoseconds in which at least one permute was in flight among one
    chip's `(name, start ns, duration ns)` ops."""
    marks = []
    for name, s, d in events:
        if name in (START, SYNC):
            marks.append((s, 1))
        if name in (DONE, SYNC):
            marks.append((s + d, -1))
    total, depth, since = 0, 0, 0
    for t, step in sorted(marks):
        if depth > 0:
            total += t - since
        depth, since = max(depth + step, 0), t
    return total


def read(ctx):
    t, program = ctx["trace"], ctx.get("program")
    if t is None or program is None or not ctx["epochs"]:
        return None
    nbytes = program.counters.get("gas/dist/exchange_bytes", 0)
    secs = sum(in_flight_ns(ev) for ev in t.ops) / 1e9
    if nbytes <= 0 or secs <= 0:
        return None
    return nbytes * ctx["epochs"] / secs / 1e9
