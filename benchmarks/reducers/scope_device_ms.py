"""Device milliseconds a traced tick under scopes of the program's own naming.

The program's ``observability/device_scopes.tables()`` says, for every
compiled program its hot path ran, which scope each instruction of the
optimized HLO belongs to; a device event's name is its instruction's whole
HLO line, so its instruction name, result type and operand names join the
two (the name and type alone meet again in another program: the search's and
the preparation's ``multiply_reduce_fusion`` over the same rows). Each instant
of device time inside the traced window is charged **once, to the innermost
event** (a ``while`` and the operations of its body both lie on the ``XLA
Ops`` line), and an event to its instruction's scope: the innermost name of
the vocabulary on its ``op_name`` path, a fusion to the scope most of its
instructions carry.

Value: the seconds under the scopes the metric's file names, summed over the
traced window, over the traced ticks. Extras: ``device_ms_by_scope`` (the
whole split: every name of the vocabulary, ``(no scope)``, and what the join
could not place, so the values add up to the device's busy time a tick),
``matched_pct`` (device time whose event found its instruction under one
scope), ``ambiguous_pct`` (the same name, type and operands under two scopes
in different programs: reported, never guessed), ``mixed_pct`` (device time in
fusions whose instructions span several scopes), ``no_scope_ops`` (the
largest operations outside every scope) and ``tables_s`` (what ``tables()`` took,
after the window).

Raises where the file names a scope outside the program's vocabulary (a
rename must not fall silent) or where ``matched_pct`` is under
``MIN_MATCHED``; a scope of the vocabulary without device time reads 0.0.
A program without ``device_scopes`` has nothing to read: None.
"""

import time
import weakref

MIN_MATCHED = 98.0
AMBIGUOUS = "(ambiguous)"
UNMATCHED = "(not in a table)"

_last = None  # (the last trace read, weakly; its split): a cell's metrics share one reading


def innermost_seconds(events):
    """{key: seconds} of ``events`` (start, end, key): every instant in which
    an event is open goes to the one that started last among those open."""
    out, stack, cursor = {}, [], float("-inf")

    def charge(until):
        nonlocal cursor
        while stack and cursor < until:
            end, key = stack[-1]
            if end > cursor:
                upto = min(end, until)
                out[key] = out.get(key, 0.0) + (upto - cursor)
                cursor = upto
            if end <= until:
                stack.pop()
        cursor = max(cursor, until)

    for start, end, key in sorted(events, key=lambda e: (e[0], -e[1])):
        charge(start)
        stack.append((end, key))
    charge(float("inf"))
    return out


def scopes_by_key(tables):
    """{(instruction name, result type, operand names): the scopes it has in
    any program}, and the keys of the fusions whose instructions span
    several scopes."""
    found, mixed = {}, set()
    for rows in tables.values():
        for row in rows:
            key = (row.name, row.type, row.operands)
            found.setdefault(key, set()).add(row.scope)
            if row.spans > 1:
                mixed.add(key)
    return found, mixed


def split(trace, tables, device_scopes):
    """Seconds of the traced window by scope, averaged over the devices, the
    seconds by event name of what lies outside every scope, and the seconds
    in fusions that span several scopes (charged to the one most of their
    instructions carry)."""
    window = trace.window
    known, mixed = scopes_by_key(tables)
    by_scope = dict.fromkeys(device_scopes.VOCABULARY + (device_scopes.NO_SCOPE, AMBIGUOUS, UNMATCHED), 0.0)
    outside, spanning = {}, 0.0
    for ops in trace.device_ops.values():
        clipped = [
            (max(a, window[0]), min(b, window[1]), name)
            for a, b, name in ops
            if min(b, window[1]) > max(a, window[0])
        ]
        for name, seconds in innermost_seconds(clipped).items():
            parsed = device_scopes.parse_instruction(name)
            key = (parsed[0], parsed[1], parsed[3]) if parsed else None
            scopes = known.get(key)
            if not scopes:
                scope = UNMATCHED
            elif len(scopes) > 1:
                scope = AMBIGUOUS
            else:
                (scope,) = scopes
            by_scope[scope] += seconds
            if scope not in device_scopes.VOCABULARY:
                outside[name] = outside.get(name, 0.0) + seconds
            elif key in mixed:
                spanning += seconds
    devices = len(trace.device_ops)
    return (
        {scope: seconds / devices for scope, seconds in by_scope.items()},
        {name: seconds / devices for name, seconds in outside.items()},
        spanning / devices,
    )


def reduce(context, scopes):
    global _last
    window = context.trace.window
    if window is None or not context.trace.device_ops:
        return None
    try:
        from pathway_tpu.observability import device_scopes
    except ImportError:  # a program from before the vocabulary
        return None
    unknown = [s for s in scopes if s not in device_scopes.VOCABULARY]
    if unknown:
        raise ValueError(f"{unknown} are not in the program's device_scopes.VOCABULARY")
    if _last is None or _last[0]() is not context.trace:
        start = time.perf_counter()
        tables = device_scopes.tables()
        tables_s = time.perf_counter() - start
        _last = (weakref.ref(context.trace), split(context.trace, tables, device_scopes) + (tables_s, len(tables)))
    by_scope, outside, spanning, tables_s, programs = _last[1]
    busy = sum(by_scope.values())
    if not busy:
        return None
    unplaced = by_scope[AMBIGUOUS] + by_scope[UNMATCHED]
    matched_pct = 100.0 * (1.0 - unplaced / busy)
    top = sorted(outside.items(), key=lambda kv: -kv[1])[:8]
    if matched_pct < MIN_MATCHED:
        raise RuntimeError(
            f"only {matched_pct:.2f}% of the device time joins the program's {programs} tables "
            f"under one scope; the largest operations outside: {[(n[:80], s) for n, s in top]}"
        )
    per_tick = 1e3 / len(context.ticks)
    return per_tick * sum(by_scope[s] for s in scopes), {
        "device_ms_by_scope": {scope: per_tick * seconds for scope, seconds in by_scope.items()},
        "matched_pct": matched_pct,
        "ambiguous_pct": 100.0 * by_scope[AMBIGUOUS] / busy,
        "mixed_pct": 100.0 * spanning / busy,
        "no_scope_ops": [[name[:64], seconds] for name, seconds in top],
        "tables_s": tables_s,
        "programs": programs,
    }
