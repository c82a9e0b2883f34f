"""Drive the program's own serving path: ``Scheduler.step`` over a
``ServeEngine`` built from a cell's settings, on the host clock.

Requests are handed to the scheduler only when they fall due, with their
arrival stamped at the scheduler's current (simulated) clock, so the
simulated clock never holds admission back. Every time here is
``time.perf_counter`` after the step returned, which is after the tokens
reached the host (the scheduler copies them there inside the step).

What the correctness check needs is kept as the run goes: each request's
prefill logits (the spy on ``admit_slot``), its tokens, and, for a
selecting method, the masks the plan carried after each decode call.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np

from bench import spec
from bench.traffic.common import Item
from bench.weights import served_params

clock = time.perf_counter

SELECTING = ("chunk", "topk")
CACHE_SNAPS = 2  # cohorts or requests whose KV cache the check may read


def program_config(cfg: dict, ref):
    """The registry's ModelConfig for ``cfg['arch']``, with the keys the
    file lists under ``reduced`` set to the file's values. Both ways an
    error: a key of the reference's ``key_map`` that disagrees with the
    registry and is not listed, and a listed key the map cannot set; then
    the reference's ``program_facts`` have to hold."""
    from repro.configs import get_config

    base = get_config(cfg["arch"])
    keys = {key: field for key, field in ref.key_map.items() if key in cfg}
    stray = set(cfg["reduced"]) - set(keys)
    if stray:
        raise ValueError(f"{cfg['arch']}: reduced names {sorted(stray)}, which the "
                         "reference's key_map cannot set in the program's registry")
    changes = {}
    for key, field in keys.items():
        want, have = cfg[key], getattr(base, field)
        if want != have:
            if key not in cfg["reduced"]:
                raise ValueError(f"{cfg['arch']}: {key}={want} in the configuration "
                                 f"file but {field}={have} in the program's registry")
            changes[field] = type(have)(want)
    out = dataclasses.replace(base, **changes)
    for what, want in ref.program_facts(ref.dims(cfg)).items():
        have = getattr(out, what)
        if have != want:
            raise ValueError(f"{cfg['arch']}: the program has {what}={have}, "
                             f"the configuration file {want}")
    return out


@dataclasses.dataclass
class Served:
    """One request's life as the harness saw it (host clock)."""

    item: Item
    req: object  # the program's Request
    due: float
    submitted: float
    admitted: Optional[float] = None
    prefill_logits: object = None  # device array (1, vocab)
    token_times: List[float] = dataclasses.field(default_factory=list)
    decode_calls: List[int] = dataclasses.field(default_factory=list)
    finished: Optional[float] = None
    slot: Optional[int] = None

    @property
    def tokens(self) -> List[int]:
        return list(self.req.tokens_out)


@dataclasses.dataclass
class Step:
    begin: float
    end: float
    tokens: int


class Program:
    """The system under test, built for one cell from one seed."""

    def __init__(self, cell, seed: int, wbits: Optional[int] = None):
        import jax
        from repro.models import build_model
        from repro.serving import Scheduler, ServeEngine

        e = cell.engine
        self.method = e["method"]
        self.round_tokens = int(e["round_tokens"])
        if self.method in SELECTING and self.round_tokens != 1:
            raise ValueError("a selecting method is checked against the masks of "
                             "every decode step, which the plan holds only at the "
                             "end of a round: round_tokens must be 1")
        self.ref = spec.reference_module(cell)
        self.dims = self.ref.dims(cell.cfg)
        self.cache_entries = self.ref.cache_entries(self.dims)
        self.pcfg = program_config(cell.cfg, self.ref)
        model = build_model(self.pcfg)
        want = jax.eval_shape(model.init, jax.random.key(0))
        have = jax.eval_shape(lambda: served_params(self.ref.leaves, self.dims, seed))
        if jax.tree.structure(want) != jax.tree.structure(have) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype)
                for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(have))):
            raise ValueError("bench/weights.py's tree differs from the program's "
                             f"parameters:\n{want}\n{have}")
        params = served_params(self.ref.leaves, self.dims, seed)
        self.wbits = int(e["wbits"]) if wbits is None else wbits
        self.engine = ServeEngine(
            model, params, max_seq=int(e["max_seq"]), batch_size=int(e["slots"]),
            sparsity=float(e["sparsity"]), method=self.method,
            plan_refresh_interval=int(e["plan_refresh_interval"]),
            cache_mb=float(e["cache_mb"]), backend=e["backend"], wbits=self.wbits)
        del params
        self.sched = Scheduler(self.engine, round_tokens=self.round_tokens)
        self.live: Dict[int, Served] = {}  # rid -> in flight
        self.done: List[Served] = []
        self.decode_masks: List[Dict[str, object]] = []  # per decode call
        # the cache entries the check compares, as the first finished cohorts
        # (selecting methods, keyed by their decode calls) or requests
        # (dense) left them
        self.cache_snaps: List[tuple] = []
        self._by_prompt: Dict[int, Served] = {}
        self._decoded = False
        self._spy()

    # -- spies: spans around each call into the engine, and what the check needs
    def _spy(self):
        import jax

        eng = self.engine
        admit, decode = eng.admit_slot, eng.decode_slots

        def admit_slot(slot, prompt):
            sv = self._by_prompt.pop(id(prompt))
            sv.admitted, sv.slot = clock(), slot
            with jax.profiler.TraceAnnotation("bench.admit"):
                last, sim = admit(slot, prompt)
            sv.prefill_logits = last
            return last, sim

        def decode_slots(tokens, n):
            with jax.profiler.TraceAnnotation("bench.decode"):
                out = decode(tokens, n)
            self._decoded = True
            return out

        eng.admit_slot, eng.decode_slots = admit_slot, decode_slots

    def submit(self, item: Item, due: float) -> Served:
        import jax.numpy as jnp
        from repro.serving import Request

        prompt = {"tokens": jnp.asarray(item.tokens[None, :])}
        req = Request(rid=item.rid, prompt=prompt, max_new_tokens=item.out_len,
                      arrival_s=self.sched.now_s)
        sv = Served(item, req, due=due, submitted=clock())
        self._by_prompt[id(prompt)] = sv
        self.live[item.rid] = sv
        self.sched.submit(req)
        return sv

    @property
    def idle(self) -> bool:
        return not self.live

    def step(self) -> (Step, List[Served]):
        """One scheduler iteration; returns it and the requests it finished."""
        import jax

        self._decoded = False
        t0 = clock()
        with jax.profiler.TraceAnnotation("bench.step"):
            self.sched.step()
        t1 = clock()
        call = None
        if self._decoded and self.method in SELECTING:
            plan = self.engine._plan
            self.decode_masks.append({k: plan[k]["mask"] for k in plan})
            call = len(self.decode_masks) - 1
        delivered, finished = 0, []
        for rid, sv in list(self.live.items()):
            new = len(sv.req.tokens_out) - len(sv.token_times)
            if new:
                delivered += new
                sv.token_times += [t1] * new
                sv.decode_calls += [call] * new
            if sv.req.state.value == "finished":
                sv.finished = t1
                finished.append(sv)
                self.done.append(sv)
                del self.live[rid]
        if finished and len(self.cache_snaps) < CACHE_SNAPS:
            cache = {name: self.engine.cache[name] for name in self.cache_entries}
            if self.method in SELECTING:
                # a cohort fills every slot: hold the whole cache (references
                # only; the next admission writes new arrays)
                self.cache_snaps.append((tuple(finished[0].decode_calls), cache))
            else:
                sv = finished[0]
                self.cache_snaps.append((sv, {name: a[:, sv.slot:sv.slot + 1]
                                              for name, a in cache.items()}))
        return Step(t0, t1, delivered), finished

    def decode_wall_s(self, first_stat: int) -> List[float]:
        """``StepStats.wall_s`` of the decode steps logged since index
        ``first_stat``: the engine's own time per step, synced."""
        return [s.wall_s for s in self.engine.stats[first_stat:] if s.kind == "decode"]

    def memory_peak_bytes(self) -> int:
        import jax

        # the CPU backend keeps no statistics
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in jax.local_devices())


def host_masks(decode_masks, calls) -> Dict[int, Dict[str, np.ndarray]]:
    """The plan's masks of the given decode calls, copied to the host as
    bool (layers, N) arrays."""
    return {c: {k: np.asarray(v) > 0.5 for k, v in decode_masks[c].items()}
            for c in sorted(set(calls))}
