"""Plain reference for one SM: its warps' traces through one issue port.

Independent of the program.  The model is the trace-conservative,
single-issue, fixed-latency schedule with greedy-then-oldest (GTO) issue:

- a warp's next trace entry may issue once its previous one has completed
  (issue cycle + the opcode's class latency) and not in the cycle it issued;
- each busy cycle issues exactly one instruction: the warp that issued last,
  if it is ready, else the lowest-numbered ready warp;
- when no warp is ready the clock jumps to the earliest completion, and the
  whole gap counts as a memory stall if a warp that wakes then waits on a
  load, store or atomic, else as a scoreboard stall; after a gap the greedy
  warp is forgotten;
- an issue stall is a busy cycle that left another warp ready.

:func:`schedule` returns the SM trace of ``(warp, pc, mask)``, the cycle
count, the thread instructions and the stall taxonomy.
"""
from __future__ import annotations

import heapq
from dataclasses import dataclass

from .hanoi_ref import ATOMIC_OPS, MEMORY_OPS

# opcodes that cost the control latency (control flow and NOP)
CONTROL_OPS = frozenset(range(0, 12))


@dataclass(frozen=True)
class Latencies:
    alu: int = 2
    control: int = 1
    memory: int = 30
    atomic: int = 40


@dataclass
class SmSchedule:
    sm_trace: list              # [(warp, pc, mask)]
    cycles: int
    thread_instructions: int
    busy_cycles: int
    issue_stall_cycles: int
    scoreboard_stall_cycles: int
    memory_stall_cycles: int


def _latency(op: int, lat: Latencies) -> tuple[int, bool]:
    if op in ATOMIC_OPS:
        return lat.atomic, True
    if op in MEMORY_OPS:
        return lat.memory, True
    if op in CONTROL_OPS:
        return lat.control, False
    return lat.alu, False


def schedule(traces, opcodes, lat: Latencies = Latencies()) -> SmSchedule:
    """GTO schedule of ``traces[w]`` (``[(pc, mask)]``), where
    ``opcodes[w][pc]`` is warp ``w``'s opcode at ``pc``."""
    n = len(traces)
    idx = [0] * n
    on_mem = [False] * n          # its previous entry was a memory op
    waiting: list = []            # heap of (ready_at, warp), pending warps
    ready: list = []              # heap of ready warp ids
    is_ready = [False] * n
    for w in range(n):
        if traces[w]:
            heapq.heappush(ready, w)
            is_ready[w] = True
    remaining = sum(len(t) for t in traces)
    order = []
    cycle = tinstr = busy = istall = sstall = mstall = 0
    last = 0

    def wake(now: int) -> None:
        while waiting and waiting[0][0] <= now:
            _, w = heapq.heappop(waiting)
            is_ready[w] = True
            heapq.heappush(ready, w)

    while remaining:
        wake(cycle)
        if not ready:
            start = cycle
            cycle = waiting[0][0]
            wake(cycle)
            if any(on_mem[w] for w in ready):
                mstall += cycle - start
            else:
                sstall += cycle - start
            last = -1
        if last >= 0 and is_ready[last]:
            w = last
            ready.remove(w)
            heapq.heapify(ready)
        else:
            w = heapq.heappop(ready)
        is_ready[w] = False
        pc, mask = traces[w][idx[w]]
        idx[w] += 1
        remaining -= 1
        ops = opcodes[w]
        op = int(ops[pc]) if 0 <= pc < len(ops) else 0
        lat_w, mem_w = _latency(op, lat)
        on_mem[w] = mem_w
        if idx[w] < len(traces[w]):
            heapq.heappush(waiting, (cycle + lat_w, w))
        order.append((w, pc, mask))
        tinstr += int(mask).bit_count()
        busy += 1
        last = w
        if ready:
            istall += 1
        cycle += 1
    return SmSchedule(sm_trace=order, cycles=cycle, thread_instructions=tinstr,
                      busy_cycles=busy, issue_stall_cycles=istall,
                      scoreboard_stall_cycles=sstall,
                      memory_stall_cycles=mstall)
