"""Plain reference for one warp: the paper's Hanoi mechanism (SS VII) in numpy.

A copy of the repository's numpy interpreter, kept with the benchmark so that
no change to the program under test can move the yardstick.  It imports
nothing from the program.  Programs are ``int32[L, 8]`` tables of
``[opcode, dst, src0, src1, src2, imm, pred1, pred2]``; predicates encode
``0`` = none, ``+k`` = P(k-1), ``-k`` = !P(k-1).

:func:`run_warp` returns a :class:`WarpResult` with the fields the benchmark
compares: status, steps, fuel left, finished mask, error, registers,
predicates, memory and the ``(pc, mask)`` trace.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

# opcodes (the program's ISA encoding)
NOP, EXIT, BRA, BSSY, BSYNC, BMOV_B2R, BMOV_R2B, BREAK, WARPSYNC, YIELD, \
    CALL, RET, MOV, MOVR, IADD, IADDI, IMUL, AND, OR, XOR, SHL, SHR, ISETP, \
    LANEID, LDG, STG, ATOMCAS, ATOMEXCH, ATOMADD = range(29)
ATOMIC_OPS = frozenset({ATOMCAS, ATOMEXCH, ATOMADD})
MEMORY_OPS = frozenset({LDG, STG}) | ATOMIC_OPS

_I32 = np.int32


@dataclass(frozen=True)
class Machine:
    """Shapes of the simulated warp."""

    n_threads: int = 32
    n_regs: int = 16
    n_preds: int = 4
    n_bx: int = 8
    mem_size: int = 256
    max_steps: int = 4096

    @property
    def full_mask(self) -> int:
        return (1 << self.n_threads) - 1


@dataclass
class WarpResult:
    status: str                  # ok | out_of_fuel | deadlock | error
    steps: int
    fuel_left: int
    finished: int
    error: "str | None"
    regs: np.ndarray             # int32[W, NR]
    preds: np.ndarray            # bool[W, NP]
    mem: np.ndarray              # int32[M]
    trace: list                  # [(pc, mask)]


def _popcount(m: int) -> int:
    return int(m).bit_count()


def _first_lane(m: int) -> int:
    return (m & -m).bit_length() - 1


def _lanes(m: int):
    t = 0
    while m:
        if m & 1:
            yield t
        m >>= 1
        t += 1


def _mask_vec(m: int, w: int) -> np.ndarray:
    return ((m >> np.arange(w)) & 1).astype(bool)


def _vec_mask(v: np.ndarray) -> int:
    return int(sum(1 << int(t) for t in np.flatnonzero(v)))


def _cmp(a, b, code: int):
    return (a == b, a != b, a < b, a <= b, a > b, a >= b)[code]


class _Arch:
    """Registers, predicates, memory and lane ids of one warp, and the ALU."""

    def __init__(self, m: Machine, regs, mem, lane_ids):
        w = m.n_threads
        self.m = m
        self.regs = (np.zeros((w, m.n_regs), _I32) if regs is None
                     else np.array(regs, _I32).reshape(w, m.n_regs))
        self.preds = np.zeros((w, m.n_preds), dtype=bool)
        self.mem = (np.zeros(m.mem_size, _I32) if mem is None
                    else np.array(mem, _I32).reshape(m.mem_size))
        self.lane_ids = (np.arange(w, dtype=_I32) if lane_ids is None
                         else np.array(lane_ids, _I32).reshape(w))

    def _pred(self, p: int) -> np.ndarray:
        if p == 0:
            return np.ones(self.m.n_threads, dtype=bool)
        if p > 0:
            return self.preds[:, p - 1]
        return ~self.preds[:, -p - 1]

    def exec_mask(self, amask: int, p1: int, p2: int) -> int:
        return amask & _vec_mask(self._pred(p1) & self._pred(p2))

    def alu(self, op: int, f, exec_m: int) -> None:
        ev = _mask_vec(exec_m, self.m.n_threads)
        R, M, size = self.regs, self.mem, self.m.mem_size
        dst, s0, s1, s2, imm = f[1], f[2], f[3], f[4], f[5]
        if op == NOP:
            return
        if op == MOV:
            R[ev, dst] = _I32(imm)
        elif op == MOVR:
            R[ev, dst] = R[ev, s0]
        elif op == IADD:
            R[ev, dst] = R[ev, s0] + R[ev, s1]
        elif op == IADDI:
            R[ev, dst] = R[ev, s0] + _I32(imm)
        elif op == IMUL:
            R[ev, dst] = R[ev, s0] * R[ev, s1]
        elif op == AND:
            R[ev, dst] = R[ev, s0] & R[ev, s1]
        elif op == OR:
            R[ev, dst] = R[ev, s0] | R[ev, s1]
        elif op == XOR:
            R[ev, dst] = R[ev, s0] ^ R[ev, s1]
        elif op == SHL:
            R[ev, dst] = R[ev, s0] << (imm & 31)
        elif op == SHR:
            R[ev, dst] = (R[ev, s0].astype(np.uint32) >> (imm & 31)).astype(_I32)
        elif op == ISETP:
            b = _I32(imm) if s1 == -1 else R[ev, s1]
            self.preds[ev, dst] = _cmp(R[ev, s0], b, s2)
        elif op == LANEID:
            R[ev, dst] = self.lane_ids[ev]
        elif op == LDG:
            R[ev, dst] = M[(R[ev, s0] + imm) % size]
        elif op == STG:
            for t in _lanes(exec_m):          # lane-serialized, lowest first
                M[(int(R[t, s0]) + imm) % size] = R[t, s1]
        elif op in ATOMIC_OPS:
            for t in _lanes(exec_m):
                a = (int(R[t, s0]) + imm) % size
                old = M[a]
                if op == ATOMCAS:
                    if old == R[t, s1]:
                        M[a] = R[t, s2]
                elif op == ATOMEXCH:
                    M[a] = R[t, s1]
                else:  # ATOMADD wraps in int32, as a 32-bit atomic does
                    M[a] = _I32((int(old) + int(R[t, s1]) + 2**31) % 2**32
                                - 2**31)
                R[t, dst] = old
        else:
            raise ValueError(f"no ALU semantics for opcode {op}")


def run_warp(program, m: Machine, *, regs=None, mem=None, lane_ids=None,
             majority_first: bool = True) -> WarpResult:
    """Run one warp to completion under Hanoi (no oracle skips)."""
    prog = np.asarray(program, dtype=np.int64)
    L = prog.shape[0]
    NB, FULL = m.n_bx, m.full_mask
    st = _Arch(m, regs, mem, lane_ids)

    ws: list[list[int]] = [[0, FULL]]          # warp-split stack [pc, mask]
    rec: list[list[int]] = []                  # reconvergence stack [pc, bx]
    bx_val = [0] * NB
    bx_valid = [False] * NB
    waiting = finished = 0
    error = None
    trace: list[tuple[int, int]] = []
    fuel, steps = m.max_steps, 0
    while fuel > 0:
        fuel -= 1
        # reconvergence check first: REC top ready -> reconverge
        if rec:
            rpc, b = rec[-1]
            if bx_valid[b]:
                live = bx_val[b] & ~finished
                if (live & ~waiting) == 0:
                    rec.pop()
                    bx_valid[b] = False
                    waiting &= ~live
                    if live:
                        ws.append([rpc + 1, live])
                    continue
        if not ws:
            break
        pc, amask = ws[-1]
        if pc < 0 or pc >= L:                  # fell off: implicit EXIT
            finished |= amask
            for x in range(NB):
                if bx_valid[x]:
                    bx_val[x] &= ~amask
            ws.pop()
            continue
        f = tuple(int(v) for v in prog[pc])
        op = f[0]
        exec_m = st.exec_mask(amask, f[6], f[7])
        trace.append((pc, amask))
        steps += 1
        if op == BRA:
            taken, ft = exec_m, amask & ~exec_m
            if taken == 0:
                ws[-1][0] = pc + 1
            elif ft == 0:
                ws[-1][0] = f[5]
            else:
                ws.pop()
                ent_t, ent_f = [f[5], taken], [pc + 1, ft]
                if majority_first and _popcount(ft) > _popcount(taken):
                    ws.append(ent_t)
                    ws.append(ent_f)
                else:
                    ws.append(ent_f)
                    ws.append(ent_t)
        elif op == EXIT:
            finished |= exec_m
            for x in range(NB):
                if bx_valid[x]:
                    bx_val[x] &= ~exec_m
            rem = amask & ~exec_m
            if rem == 0:
                ws.pop()
            else:
                ws[-1] = [pc + 1, rem]
        elif op == BSSY:
            if exec_m:
                b = f[1]
                bx_val[b] = amask
                bx_valid[b] = True
                rec.append([f[5], b])
            ws[-1][0] = pc + 1
        elif op == BSYNC:
            b = f[1]
            if rec and rec[-1][1] == b:
                ws.pop()
                waiting |= amask
            elif len(ws) >= 2:                 # deeper sync point: park
                ws[-1], ws[-2] = ws[-2], ws[-1]
        elif op == WARPSYNC:
            msk = (f[5] if f[2] == -1
                   else int(st.regs[_first_lane(exec_m or amask), f[2]])) & FULL
            if not any(e[0] == pc for e in rec):
                free = next((x for x in range(NB) if not bx_valid[x]), None)
                if free is None:
                    error = error or "WARPSYNC: no free Bx register"
                    ws[-1][0] = pc + 1
                    continue
                bx_val[free] = msk & ~finished
                bx_valid[free] = True
                rec.append([pc, free])
                ws.pop()
                waiting |= amask
            elif rec and rec[-1][0] == pc:
                ws.pop()
                waiting |= amask
            elif len(ws) >= 2:
                ws[-1], ws[-2] = ws[-2], ws[-1]
        elif op == BREAK:
            bx_val[f[1]] &= ~exec_m
            ws[-1][0] = pc + 1
        elif op == BMOV_B2R:
            if exec_m:
                ev = _mask_vec(exec_m, m.n_threads)
                st.regs[ev, f[1]] = np.int64(bx_val[f[2]]).astype(_I32)
                bx_valid[f[2]] = False
            ws[-1][0] = pc + 1
        elif op == BMOV_R2B:
            if exec_m:
                v = int(st.regs[_first_lane(exec_m), f[2]])
                bx_val[f[1]] = v & FULL & ~finished
                bx_valid[f[1]] = True
            ws[-1][0] = pc + 1
        elif op == YIELD:
            ws[-1][0] = pc + 1
            if len(ws) >= 2 and rec:
                rpc, b = rec[-1]
                if bx_valid[b]:
                    live = bx_val[b] & ~finished
                    if ((ws[-1][1] | ws[-2][1]) & ~live) == 0:
                        ws[-1], ws[-2] = ws[-2], ws[-1]
        elif op == CALL:
            ws[-1][0] = f[5] if exec_m else pc + 1
        elif op == RET:
            ws[-1][0] = (int(st.regs[_first_lane(exec_m), f[2]])
                         if exec_m else pc + 1)
        else:
            st.alu(op, f, exec_m)
            ws[-1][0] = pc + 1

    fuel = max(0, fuel)
    if error:
        status = "error"
    elif fuel == 0:
        status = "out_of_fuel"
    elif (finished & FULL) == FULL:
        status = "ok"
    else:
        status = "deadlock"
    return WarpResult(status=status, steps=steps, fuel_left=fuel,
                      finished=finished, error=error, regs=st.regs,
                      preds=st.preds, mem=st.mem, trace=trace)
