"""Hanoi as a vectorized JAX state machine.

This is the TPU-native rendering of the paper's SS VII microarchitecture: all
control flow of the *simulated* machine (WS/REC stacks, Bx file, waiting and
finished masks) is data, the scheduler loop is a ``lax.while_loop`` and the
per-opcode semantics a ``lax.switch`` — so the whole simulator JIT-compiles
and ``vmap``s over warps.  Trace-driven C++ GPU simulators execute one warp
at a time on a scalar host; here thousands of warps step in lockstep on SIMD
hardware, which is exactly the control-flow-to-dataflow transformation the
paper studies, applied to the simulator itself.

Semantics are property-tested for exact equivalence with the numpy reference
(`repro.core.interp.run_hanoi`) over random structured programs and the full
benchmark suite.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .isa import CMP_EQ, CMP_GE, CMP_GT, CMP_LE, CMP_LT, CMP_NE, MachineConfig, Op

U32 = jnp.uint32
I32 = jnp.int32

# error bit flags
ERR_NO_FREE_BX = 1


class HanoiState(NamedTuple):
    # warp-split stack (SS VII: one entry per path; top = executing path)
    ws_pc: jax.Array      # i32[SD]
    ws_mask: jax.Array    # u32[SD]
    ws_top: jax.Array     # i32  (-1 = empty)
    # reconvergence stack (one entry per pending reconvergence point)
    rec_pc: jax.Array     # i32[SD]
    rec_bx: jax.Array     # i32[SD]
    rec_top: jax.Array    # i32
    # Bx register file
    bx_val: jax.Array     # u32[NB]
    bx_valid: jax.Array   # bool[NB]
    waiting: jax.Array    # u32
    finished: jax.Array   # u32
    # architectural state
    regs: jax.Array       # i32[W, NR]
    preds: jax.Array      # bool[W, NP]
    mem: jax.Array        # i32[M]
    lane_ids: jax.Array   # i32[W]
    # trace ring + bookkeeping
    trace_pc: jax.Array   # i32[T]
    trace_mask: jax.Array  # u32[T]
    trace_n: jax.Array    # i32
    steps: jax.Array      # i32
    fuel: jax.Array       # i32
    halted: jax.Array     # bool
    error: jax.Array      # i32 bit flags


def _lane_bits(cfg: MachineConfig) -> jax.Array:
    return (U32(1) << jnp.arange(cfg.n_threads, dtype=U32))


def _mask_to_vec(mask: jax.Array, cfg: MachineConfig) -> jax.Array:
    return (mask & _lane_bits(cfg)) != 0


def _vec_to_mask(vec: jax.Array, cfg: MachineConfig) -> jax.Array:
    return jnp.sum(jnp.where(vec, _lane_bits(cfg), U32(0)), dtype=U32)


def _first_lane(mask: jax.Array, cfg: MachineConfig) -> jax.Array:
    return jnp.argmax(_mask_to_vec(mask, cfg)).astype(I32)


def _popcount(mask: jax.Array) -> jax.Array:
    return lax.population_count(mask).astype(I32)


def _put(arr: jax.Array, i, v) -> jax.Array:
    """``arr.at[i].set(v)`` on axis 0, as a one-hot select.

    It gives what the scatter gives — a negative ``i`` counts from the end,
    an ``i`` past the end changes nothing — but lowers to a dense
    elementwise op.  Under ``vmap`` every handler of the step runs for
    every warp, so these updates also run with whatever indices the warp's
    current instruction holds.  As batched scatters they gave up to a
    third of the warps of a 4096-warp batch wrong results on a TPU v5e,
    while the same warps in a batch of 64 were right; as selects all are
    right.
    """
    n = arr.shape[0]
    i = jnp.where(i < 0, i + n, i)
    hit = jnp.arange(n) == i
    return jnp.where(hit.reshape((n,) + (1,) * (arr.ndim - 1)), v, arr)


def init_state(program_len: int, cfg: MachineConfig, *,
               init_regs=None, init_mem=None, lane_ids=None,
               active0: int | None = None) -> HanoiState:
    W, SD, T = cfg.n_threads, cfg.n_threads + 2, cfg.max_steps
    full = U32(cfg.full_mask if active0 is None else active0)
    ws_pc = jnp.zeros(SD, I32)
    ws_mask = jnp.zeros(SD, U32).at[0].set(full)
    regs = (jnp.zeros((W, cfg.n_regs), I32) if init_regs is None
            else jnp.asarray(init_regs, I32).reshape(W, cfg.n_regs))
    mem = (jnp.zeros(cfg.mem_size, I32) if init_mem is None
           else jnp.asarray(init_mem, I32).reshape(cfg.mem_size))
    lanes = (jnp.arange(W, dtype=I32) if lane_ids is None
             else jnp.asarray(lane_ids, I32).reshape(W))
    return HanoiState(
        ws_pc=ws_pc, ws_mask=ws_mask, ws_top=jnp.asarray(0, I32),
        rec_pc=jnp.zeros(SD, I32), rec_bx=jnp.zeros(SD, I32),
        rec_top=jnp.asarray(-1, I32),
        bx_val=jnp.zeros(cfg.n_bx, U32),
        bx_valid=jnp.zeros(cfg.n_bx, bool),
        waiting=U32(0), finished=U32(0),
        regs=regs, preds=jnp.zeros((W, cfg.n_preds), bool), mem=mem,
        lane_ids=lanes,
        trace_pc=jnp.full(T, -1, I32), trace_mask=jnp.zeros(T, U32),
        trace_n=jnp.asarray(0, I32), steps=jnp.asarray(0, I32),
        fuel=jnp.asarray(cfg.max_steps, I32),
        halted=jnp.asarray(False), error=jnp.asarray(0, I32))


def _pred_vec(preds: jax.Array, p: jax.Array, cfg: MachineConfig) -> jax.Array:
    """Predicate guard vector for encoded predicate field p (0 / +k / -k)."""
    idx = jnp.abs(p) - 1
    val = preds[:, jnp.clip(idx, 0, cfg.n_preds - 1)]
    return jnp.where(p == 0, True, jnp.where(p > 0, val, ~val))


def _cmp(a, b, code):
    return lax.switch(jnp.clip(code, 0, 5), [
        lambda: a == b, lambda: a != b, lambda: a < b,
        lambda: a <= b, lambda: a > b, lambda: a >= b])


def _lane_serial_mem(op, mem, regs, ev, s0, s1, s2, imm):
    """One warp's STG or atomic (CAS, EXCH, ADD), lane-parallel.

    Returns ``(mem, old)``: memory as if the ``ev`` lanes ran one at a time
    in ascending order, as the numpy reference's loop runs them, and
    ``old[t]`` the word lane t's atomic read.  Lane t reads only its own
    register row, and before it writes it, so every operand comes from the
    registers the instruction started with.  Per address the last lane's
    value is the one memory keeps.  STG and EXCH store ``b``; ATOMADD's lane
    t reads the word plus the ``b`` of the earlier lanes on its address
    (int32 wraps, so the order of the sum does not matter); EXCH's reads the
    ``b`` of the nearest earlier lane on its address.  CAS has no closed
    form: a loop over the lanes carries only the word each lane's address
    holds, never the memory or the registers.  Memory is written once, as
    a one-hot select, not a scatter (see :func:`_put`); one-hot sums also
    stand in for gathers, which ran slower on a TPU v5e.
    """
    W, M = ev.shape[0], mem.shape[0]
    lane = jnp.arange(W, dtype=I32)
    addr = (regs[:, jnp.clip(s0, 0)] + imm) % M
    b = regs[:, jnp.clip(s1, 0)]
    c = regs[:, jnp.clip(s2, 0)]
    at = addr[:, None] == jnp.arange(M)                   # [W, M]
    word = jnp.sum(jnp.where(at, mem, 0), axis=1, dtype=I32)  # mem[addr]
    same = ev[None, :] & (addr[:, None] == addr[None, :])  # [t, t']
    before = same & (lane[None, :] < lane[:, None])
    last = ev & ~jnp.any(same & (lane[None, :] > lane[:, None]), axis=1)

    nearest = jnp.max(jnp.where(before, lane[None, :], -1), axis=1)
    old_exch = jnp.where(
        nearest >= 0,
        jnp.sum(jnp.where(lane[None, :] == nearest[:, None], b, 0), axis=1,
                dtype=I32),
        word)
    old_add = word + jnp.sum(jnp.where(before, b, 0), axis=1, dtype=I32)

    def chain(t, carry):          # CAS: lane t swaps on what lanes < t left
        cur, olds = carry
        o = cur[t]
        cur = jnp.where(same[:, t], jnp.where(o == b[t], c[t], o), cur)
        return cur, jnp.where(lane == t, o, olds)
    cur, old_cas = lax.fori_loop(0, W, chain, (word, word))

    is_cas, is_add = op == Op.ATOMCAS, op >= Op.ATOMADD
    old = jnp.where(is_cas, old_cas, jnp.where(is_add, old_add, old_exch))
    new = jnp.where(is_cas, cur, jnp.where(is_add, old_add + b, b))
    hit = ev[:, None] & at
    kept = jnp.sum(jnp.where(hit & last[:, None], new[:, None], 0), axis=0,
                   dtype=I32)
    return jnp.where(jnp.any(hit, axis=0), kept, mem), old


# ---------------------------------------------------------------------------
# the scheduler step
# ---------------------------------------------------------------------------

def _step(s: HanoiState, program: jax.Array, cfg: MachineConfig,
          skip_vec: jax.Array, majority_first: bool) -> HanoiState:
    W, NB = cfg.n_threads, cfg.n_bx
    FULL = U32(cfg.full_mask)

    # ---- 1) reconvergence check (SS VII-B) --------------------------------
    has_rec = s.rec_top >= 0
    rtop = jnp.clip(s.rec_top, 0)
    rbx = s.rec_bx[rtop]
    rvalid = has_rec & s.bx_valid[rbx]
    live = s.bx_val[rbx] & ~s.finished
    can_reconv = rvalid & ((live & ~s.waiting) == 0)

    def do_reconv(s: HanoiState) -> HanoiState:
        new_top = jnp.where(live != 0, s.ws_top + 1, s.ws_top)
        return s._replace(
            rec_top=s.rec_top - 1,
            bx_valid=_put(s.bx_valid, rbx, False),
            waiting=s.waiting & ~live,
            ws_pc=jnp.where(live != 0,
                            _put(s.ws_pc, s.ws_top + 1, s.rec_pc[rtop] + 1),
                            s.ws_pc),
            ws_mask=jnp.where(live != 0,
                              _put(s.ws_mask, s.ws_top + 1, live),
                              s.ws_mask),
            ws_top=new_top,
            fuel=s.fuel - 1)

    # ---- 2) execute top-of-WS ----------------------------------------------
    def do_exec(s: HanoiState) -> HanoiState:
        empty = s.ws_top < 0
        top = jnp.clip(s.ws_top, 0)
        pc = s.ws_pc[top]
        amask = s.ws_mask[top]
        oob = (pc < 0) | (pc >= program.shape[0])

        def halt(s):
            return s._replace(halted=True, fuel=s.fuel - 1)

        def implicit_exit(s):   # fell off the program: treat as EXIT
            bxv = jnp.where(s.bx_valid, s.bx_val & ~amask, s.bx_val)
            return s._replace(finished=s.finished | amask, bx_val=bxv,
                              ws_top=s.ws_top - 1, fuel=s.fuel - 1)

        def exec_instr(s: HanoiState) -> HanoiState:
            f = program[jnp.clip(pc, 0, program.shape[0] - 1)]
            op, dst, s0, s1, s2, imm, p1, p2 = (f[i] for i in range(8))
            guard = (_pred_vec(s.preds, p1, cfg)
                     & _pred_vec(s.preds, p2, cfg))
            execm = amask & _vec_to_mask(guard, cfg)
            ev = _mask_to_vec(execm, cfg)
            # trace
            s = s._replace(
                trace_pc=_put(s.trace_pc, s.trace_n, pc),
                trace_mask=_put(s.trace_mask, s.trace_n, amask),
                trace_n=s.trace_n + 1, steps=s.steps + 1, fuel=s.fuel - 1)

            def set_pc(st, v):
                return st._replace(ws_pc=_put(st.ws_pc, top, v))

            def h_fallthrough(st):
                return set_pc(st, pc + 1)

            def h_bra(st):
                taken, ft = execm, amask & ~execm
                n_t, n_f = _popcount(taken), _popcount(ft)

                def uniform(st):
                    return set_pc(st, jnp.where(taken == 0, pc + 1, imm))

                def diverge(st):
                    maj_is_ft = jnp.asarray(majority_first) & (n_f > n_t)
                    pc_lo = jnp.where(maj_is_ft, imm, pc + 1)
                    m_lo = jnp.where(maj_is_ft, taken, ft)
                    pc_hi = jnp.where(maj_is_ft, pc + 1, imm)
                    m_hi = jnp.where(maj_is_ft, ft, taken)
                    return st._replace(
                        ws_pc=_put(_put(st.ws_pc, top, pc_lo), top + 1,
                                   pc_hi),
                        ws_mask=_put(_put(st.ws_mask, top, m_lo), top + 1,
                                     m_hi),
                        ws_top=st.ws_top + 1)

                return lax.cond((taken == 0) | (ft == 0), uniform, diverge, st)

            def h_exit(st):
                fin = execm
                bxv = jnp.where(st.bx_valid, st.bx_val & ~fin, st.bx_val)
                rem = amask & ~fin
                st = st._replace(finished=st.finished | fin, bx_val=bxv)
                return lax.cond(
                    rem == 0,
                    lambda st: st._replace(ws_top=st.ws_top - 1),
                    lambda st: st._replace(
                        ws_pc=_put(st.ws_pc, top, pc + 1),
                        ws_mask=_put(st.ws_mask, top, rem)),
                    st)

            def h_bssy(st):
                def doit(st):
                    return st._replace(
                        bx_val=_put(st.bx_val, dst, amask),
                        bx_valid=_put(st.bx_valid, dst, True),
                        rec_pc=_put(st.rec_pc, st.rec_top + 1, imm),
                        rec_bx=_put(st.rec_bx, st.rec_top + 1, dst),
                        rec_top=st.rec_top + 1)
                st = lax.cond(execm != 0, doit, lambda st: st, st)
                return set_pc(st, pc + 1)

            def _park(st):
                """Sync point is not REC-top: retry after the sibling."""
                def swap(st):
                    a, b = st.ws_pc[top], st.ws_pc[top - 1]
                    ma, mb = st.ws_mask[top], st.ws_mask[top - 1]
                    return st._replace(
                        ws_pc=_put(_put(st.ws_pc, top, b), top - 1, a),
                        ws_mask=_put(_put(st.ws_mask, top, mb), top - 1,
                                     ma))
                return lax.cond(st.ws_top >= 1, swap, lambda st: st, st)

            def h_bsync(st):
                b = dst
                at_top = (st.rec_top >= 0) & (st.rec_bx[rtop_of(st)] == b)
                lv = st.bx_val[b] & ~st.finished
                skip = skip_vec[jnp.clip(pc, 0, skip_vec.shape[0] - 1)] \
                    & st.bx_valid[b] & (lv != amask)

                def do_skip(st):   # Turing-oracle heuristic (SS IX)
                    return set_pc(st._replace(
                        bx_val=_put(st.bx_val, b, st.bx_val[b] & ~amask)),
                        pc + 1)

                def do_wait(st):
                    return st._replace(ws_top=st.ws_top - 1,
                                       waiting=st.waiting | amask)

                return lax.cond(skip, do_skip,
                                lambda st: lax.cond(at_top, do_wait, _park,
                                                    st), st)

            def rtop_of(st):
                return jnp.clip(st.rec_top, 0)

            def h_warpsync(st):
                m = jnp.where(
                    s0 == -1, imm.astype(U32),
                    st.regs[_first_lane(jnp.where(execm != 0, execm, amask),
                                        cfg), jnp.clip(s0, 0)].astype(U32)
                ) & FULL
                idx = jnp.arange(st.rec_pc.shape[0])
                present = jnp.any((idx <= st.rec_top) & (st.rec_pc == pc))
                at_top = (st.rec_top >= 0) & (st.rec_pc[rtop_of(st)] == pc)

                def push_new(st):
                    free_any = jnp.any(~st.bx_valid)
                    free = jnp.argmin(st.bx_valid).astype(I32)

                    def ok(st):
                        return st._replace(
                            bx_val=_put(st.bx_val, free, m & ~st.finished),
                            bx_valid=_put(st.bx_valid, free, True),
                            rec_pc=_put(st.rec_pc, st.rec_top + 1, pc),
                            rec_bx=_put(st.rec_bx, st.rec_top + 1, free),
                            rec_top=st.rec_top + 1,
                            ws_top=st.ws_top - 1,
                            waiting=st.waiting | amask)

                    def err(st):
                        return set_pc(st._replace(
                            error=st.error | ERR_NO_FREE_BX), pc + 1)

                    return lax.cond(free_any, ok, err, st)

                def join(st):
                    return st._replace(ws_top=st.ws_top - 1,
                                       waiting=st.waiting | amask)

                return lax.cond(
                    ~present, push_new,
                    lambda st: lax.cond(at_top, join, _park, st), st)

            def h_break(st):
                return set_pc(st._replace(
                    bx_val=_put(st.bx_val, dst, st.bx_val[dst] & ~execm)),
                    pc + 1)

            def h_bmov_b2r(st):
                def doit(st):
                    v = st.bx_val[s0].astype(I32)
                    return st._replace(
                        regs=jnp.where(ev[:, None]
                                       & (jnp.arange(cfg.n_regs) == dst),
                                       v, st.regs),
                        bx_valid=_put(st.bx_valid, s0, False))
                return set_pc(lax.cond(execm != 0, doit, lambda st: st, st),
                              pc + 1)

            def h_bmov_r2b(st):
                def doit(st):
                    v = st.regs[_first_lane(execm, cfg), jnp.clip(s0, 0)]
                    return st._replace(
                        bx_val=_put(st.bx_val, dst,
                                    v.astype(U32) & FULL & ~st.finished),
                        bx_valid=_put(st.bx_valid, dst, True))
                return set_pc(lax.cond(execm != 0, doit, lambda st: st, st),
                              pc + 1)

            def h_yield(st):
                st = set_pc(st, pc + 1)

                def try_swap(st):
                    rb = st.rec_bx[rtop_of(st)]
                    lv = st.bx_val[rb] & ~st.finished
                    sib = ((st.rec_top >= 0) & st.bx_valid[rb]
                           & (((st.ws_mask[top] | st.ws_mask[top - 1])
                               & ~lv) == 0))

                    def swap(st):
                        a, b = st.ws_pc[top], st.ws_pc[top - 1]
                        ma, mb = st.ws_mask[top], st.ws_mask[top - 1]
                        return st._replace(
                            ws_pc=_put(_put(st.ws_pc, top, b), top - 1, a),
                            ws_mask=_put(_put(st.ws_mask, top, mb),
                                         top - 1, ma))
                    return lax.cond(sib, swap, lambda st: st, st)

                return lax.cond(st.ws_top >= 1, try_swap, lambda st: st, st)

            def h_call(st):
                return set_pc(st, jnp.where(execm != 0, imm, pc + 1))

            def h_ret(st):
                tgt = st.regs[_first_lane(jnp.where(execm != 0, execm, amask),
                                          cfg), jnp.clip(s0, 0)]
                return set_pc(st, jnp.where(execm != 0, tgt, pc + 1))

            # ---- ALU / memory ----------------------------------------------
            def upd_reg(st, val_vec):
                return st._replace(regs=jnp.where(
                    ev[:, None] & (jnp.arange(cfg.n_regs) == dst),
                    val_vec[:, None], st.regs))

            R = s.regs

            def h_mov(st):
                return set_pc(upd_reg(st, jnp.full(W, imm, I32)), pc + 1)

            def h_movr(st):
                return set_pc(upd_reg(st, R[:, jnp.clip(s0, 0)]), pc + 1)

            def _bin(fn):
                def h(st):
                    a, b = R[:, jnp.clip(s0, 0)], R[:, jnp.clip(s1, 0)]
                    return set_pc(upd_reg(st, fn(a, b)), pc + 1)
                return h

            def h_iaddi(st):
                return set_pc(upd_reg(st, R[:, jnp.clip(s0, 0)] + imm), pc + 1)

            def h_shl(st):
                return set_pc(
                    upd_reg(st, R[:, jnp.clip(s0, 0)] << (imm & 31)), pc + 1)

            def h_shr(st):
                v = (R[:, jnp.clip(s0, 0)].astype(U32) >> (imm & 31).astype(U32))
                return set_pc(upd_reg(st, v.astype(I32)), pc + 1)

            def h_isetp(st):
                a = R[:, jnp.clip(s0, 0)]
                b = jnp.where(s1 == -1, jnp.full(W, imm, I32),
                              R[:, jnp.clip(s1, 0)])
                res = _cmp(a, b, s2)
                preds = jnp.where(
                    ev[:, None] & (jnp.arange(cfg.n_preds) == dst),
                    res[:, None], st.preds)
                return set_pc(st._replace(preds=preds), pc + 1)

            def h_laneid(st):
                return set_pc(upd_reg(st, st.lane_ids), pc + 1)

            def h_ldg(st):
                addr = (R[:, jnp.clip(s0, 0)] + imm) % cfg.mem_size
                return set_pc(upd_reg(st, st.mem[addr]), pc + 1)

            def h_mem(st):
                mem, old = _lane_serial_mem(op, st.mem, R, ev, s0, s1, s2,
                                            imm)
                wr = ev & (op != Op.STG)
                return set_pc(st._replace(mem=mem, regs=jnp.where(
                    wr[:, None] & (jnp.arange(cfg.n_regs) == jnp.clip(dst, 0)),
                    old[:, None], st.regs)), pc + 1)

            handlers = [
                h_fallthrough,                      # NOP
                h_exit, h_bra, h_bssy, h_bsync,
                h_bmov_b2r, h_bmov_r2b, h_break, h_warpsync, h_yield,
                h_call, h_ret,
                h_mov, h_movr,
                _bin(lambda a, b: a + b),           # IADD
                h_iaddi,
                _bin(lambda a, b: a * b),           # IMUL
                _bin(lambda a, b: a & b),           # AND
                _bin(lambda a, b: a | b),           # OR
                _bin(lambda a, b: a ^ b),           # XOR
                h_shl, h_shr, h_isetp, h_laneid,
                h_ldg, h_mem,       # STG and the atomics clip to this branch
            ]
            return lax.switch(jnp.clip(op, 0, len(handlers) - 1), handlers, s)

        return lax.cond(empty, halt,
                        lambda s: lax.cond(oob, implicit_exit, exec_instr, s),
                        s)

    return lax.cond(can_reconv, do_reconv, do_exec, s)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "majority_first"))
def _run(program: jax.Array, state: HanoiState, skip_vec: jax.Array,
         cfg: MachineConfig, majority_first: bool) -> HanoiState:
    def cond(s: HanoiState):
        return (~s.halted) & (s.fuel > 0)

    def body(s: HanoiState):
        return _step(s, program, cfg, skip_vec, majority_first)

    return lax.while_loop(cond, body, state)


def run_hanoi_jax(program: np.ndarray,
                  cfg: MachineConfig = MachineConfig(),
                  *, init_regs=None, init_mem=None, lane_ids=None,
                  active0: int | None = None,
                  bsync_skip_pcs=(), majority_first: bool = True,
                  pad_to: int | None = None) -> HanoiState:
    """JIT-compiled single-warp run.  Returns the final :class:`HanoiState`.

    ``pad_to`` pads the program table (with trailing EXITs, unreachable) to a
    fixed length so repeated calls reuse the compiled executable.
    """
    prog = np.asarray(program, dtype=np.int32)
    if pad_to is not None and prog.shape[0] < pad_to:
        pad = np.zeros((pad_to - prog.shape[0], prog.shape[1]), np.int32)
        pad[:, 0] = int(Op.EXIT)
        prog = np.concatenate([prog, pad], axis=0)
    skip = np.zeros(prog.shape[0], bool)
    for pc in bsync_skip_pcs:
        skip[pc] = True
    state = init_state(prog.shape[0], cfg, init_regs=init_regs,
                       init_mem=init_mem, lane_ids=lane_ids, active0=active0)
    return _run(jnp.asarray(prog), state, jnp.asarray(skip), cfg,
                majority_first)


def run_warps_jax(program: np.ndarray, cfg: MachineConfig,
                  init_regs: np.ndarray, init_mem: np.ndarray,
                  lane_ids: np.ndarray | None = None,
                  *, bsync_skip_pcs=(), majority_first: bool = True
                  ) -> HanoiState:
    """vmap over warps: ``init_regs`` is [n_warps, W, NR], ``init_mem`` is
    [n_warps, M] (per-warp memories), lane_ids [n_warps, W]."""
    prog = jnp.asarray(np.asarray(program, dtype=np.int32))
    skip = np.zeros(prog.shape[0], bool)
    for pc in bsync_skip_pcs:
        skip[pc] = True
    skip = jnp.asarray(skip)
    n = init_regs.shape[0]
    if lane_ids is None:
        lane_ids = np.broadcast_to(np.arange(cfg.n_threads, dtype=np.int32),
                                   (n, cfg.n_threads))

    def one(regs, mem, lanes):
        st = init_state(prog.shape[0], cfg, init_regs=regs, init_mem=mem,
                        lane_ids=lanes)
        return _run(prog, st, skip, cfg, majority_first)

    return jax.vmap(one)(jnp.asarray(init_regs, I32),
                         jnp.asarray(init_mem, I32),
                         jnp.asarray(lane_ids, I32))


def state_trace(st: HanoiState) -> list[tuple[int, int]]:
    n = int(st.trace_n)
    # .tolist() gives native ints in one C pass — per-element int() casts
    # dominated batched result assembly at scale
    return list(zip(np.asarray(st.trace_pc[:n]).tolist(),
                    np.asarray(st.trace_mask[:n]).tolist()))


def state_deadlocked(st: HanoiState, cfg: MachineConfig) -> bool:
    return bool((int(st.finished) & cfg.full_mask) != cfg.full_mask
                or int(st.fuel) <= 0)
