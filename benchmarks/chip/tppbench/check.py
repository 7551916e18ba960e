"""Whether what the timed path served is correct.

Two samples are taken from the window, both drawn from the seed:

* finished requests: the longest one, and others, first among those that
  read a page in the slow tier (pages that placement migrated);
* requests still in their lanes when the window closes, whose pages the
  program still holds: their K and V are read out of the store (pending
  migrations settled first) before the program's state is freed.

The harness keeps the pages the program chose to attend at every decode
step. Once the program's state is freed, the plain reference of the
configuration runs every sampled request teacher-forced on its prompt,
its served tokens and its chosen pages, in f32 at ``highest``. Three
numbers are compared, each with its limit in ``cells/<workload>.json``:

* ``logit_gap``: the widest gap by which a served token's logit lies below
  the reference's best over the same pages (decode step and paged
  attention; a token altered where it is produced);
* ``page_shortfall``: the widest shortfall of a chosen older page's score
  below the reference's k-th best, over the spread of older scores, or
  ``inf`` for a choice without the recent pages or with a wrong number of
  older ones (placement and selection);
* ``v0_rel_err``: the largest relative error of a first-layer V vector the
  program holds, at every position of every sampled lane in flight:
  prefill's writes, the decode step's writes and every payload that a
  migration moved. No RoPE touches it. K, and V above the first layer,
  carry the chip's f32 RoPE rounding (up to ~2e-3 past position 1,500,
  PERF.md), which is wider than the control's step, so ``kv_rel_err``,
  the same over K and V of every layer, is printed and not compared.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

from tppbench import spec, weights
from tppbench.traffic import Request

NUMBERS = ("logit_gap", "page_shortfall", "v0_rel_err")


@dataclasses.dataclass
class Snapshot:
    """A request still in its lane at the window's end, with the K and V the
    program holds for it: (L, positions, Hkv, D) each."""

    req: Request
    out: List[int]
    k: np.ndarray
    v: np.ndarray


def _draw(pool: Sequence[Request], seed: int, salt: int, k: int, size) -> List[Request]:
    """The largest request and ``k - 1`` others drawn from the seed, first
    among those that read a slow page, then among the rest."""
    if not pool or k <= 0:
        return []
    ordered = sorted(pool, key=lambda r: r.index)
    first = max(ordered, key=lambda r: (size(r), -r.index))
    rng = np.random.default_rng([int(seed) % (1 << 64), salt])
    out = [first]
    for group in ([r for r in ordered if r is not first and r.slow_steps],
                  [r for r in ordered if r is not first and not r.slow_steps]):
        n = min(k - len(out), len(group))
        if n > 0:
            out += [group[i] for i in sorted(rng.choice(len(group), size=n, replace=False))]
    return out


def sample(finished: Sequence[Request], seed: int, k: int) -> List[Request]:
    return _draw(finished, seed, 3, k, lambda r: len(r.prompt) + len(r.out))


def snapshot(slots, in_lanes: Sequence[Request], seed: int, k: int) -> List[Snapshot]:
    """K and V of sampled in-flight requests, read out of the program's store."""
    import jax.numpy as jnp

    eng = slots.engine
    eng.kv.flush_migrations()
    out = []
    for req in _draw(in_lanes, seed, 4, k, lambda r: len(r.prompt) + r.tokens):
        seq = eng.seqs[req.rid]
        frames = jnp.asarray(eng.kv.global_frames(seq.pages))
        kv = []
        for store in (eng.kv.k_store, eng.kv.v_store):
            pages = np.asarray(store[frames])  # (n, L, Hkv, P, D)
            n, L, Hkv, P, D = pages.shape
            kv.append(pages.transpose(1, 0, 3, 2, 4).reshape(L, n * P, Hkv, D)[:, :seq.cur_len])
        out.append(Snapshot(req, list(eng.requests[req.rid].out), kv[0], kv[1]))
    return out


def _rel(held: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Relative error of each (layer, position) vector."""
    L, T = held.shape[:2]
    return (np.linalg.norm((held - want).reshape(L, T, -1), axis=-1)
            / np.maximum(np.linalg.norm(want.reshape(L, T, -1), axis=-1), 1e-30))


def kv_errors(snaps: Sequence[Snapshot], cache, lanes: Sequence[int]) -> Tuple[float, float]:
    """Largest relative error of held V of the first layer, at every
    position; and of held K and V of every layer at prompt positions and
    of the first layer at decoded ones."""
    v0 = kv = 0.0
    for snap, r in zip(snaps, lanes):
        S = len(snap.req.prompt) - 1
        for name, held, ref in (("k", snap.k, cache[0]), ("v", snap.v, cache[1])):
            err = _rel(held, ref[r, :, : held.shape[1]])
            if name == "v":
                v0 = max(v0, float(err[0].max()))
            err[1:, S:] = 0.0
            kv = max(kv, float(err.max()))
    return v0, kv


def reference(cell: spec.Cell, seed: int, reqs: Sequence[Request], fed, chosen=None,
              judged=None, kind: str = "highest"):
    """The reference over ``reqs`` teacher-forced on the tokens ``fed`` and
    the pages ``chosen`` (its own choice where ``None``), judging ``judged``
    (default ``fed``): f32 at ``highest``, or the configuration's control
    precision (``bfloat16``, or ``high``: three bf16 passes for an f32
    configuration run at ``highest``)."""
    import jax
    import jax.numpy as jnp

    arch = spec.architecture(cell.root, cell.model["architecture"])
    ref = spec.reference(cell.root, arch.REFERENCE)
    dims = ref.dims_of(cell.model, cell.engine)
    dtype = jnp.bfloat16 if kind == "bfloat16" else jnp.float32
    w = weights.make(arch.leaves(cell.model), seed, jnp.dtype(dtype).name)
    with jax.default_matmul_precision("default" if kind == "bfloat16" else kind):
        judgement = ref.run(dims, w, [r.prompt for r in reqs], fed, chosen=chosen,
                            judged=judged, dtype=dtype)
    del w
    return judgement


def _values(j, snaps, cache, lanes) -> Dict[str, float]:
    v0, kv = kv_errors(snaps, cache, lanes)
    return {"logit_gap": max(g for per in j.gaps[0] for g in per),
            "page_shortfall": max(x for per in j.shortfalls for x in per),
            "v0_rel_err": v0, "kv_rel_err": kv}


def _verdict(values: Dict[str, float], limits: Dict, prefix: str = "") -> Tuple[bool, Dict]:
    """Whether every number is finite and within its limit, and the numbers
    each beside its limit (a missing limit fails)."""
    ok = True
    numbers = {}
    for n in NUMBERS:
        v, lim = values[n], limits.get(n)
        ok &= lim is not None and bool(np.isfinite(v)) and v <= float(lim)
        numbers[prefix + n] = {"value": v if np.isfinite(v) else None, "limit": lim}
    return bool(ok), numbers


def judge(cell: spec.Cell, seed: int, finished: Sequence[Request],
          snaps: Sequence[Snapshot] = (), chosen: Dict[int, List[List[int]]] = None,
          log: Callable[[str], None] = print, control: bool = False) -> Dict:
    """The verdict and the numbers compared; ``chosen`` holds the pages the
    program attended at each step of each request. With ``control``, the
    reference computed in the configuration's control precision is put in
    the program's place and judged by the same comparison: its own pages
    and its own greedy token at every position of the same prompts and
    served tokens, and its K and V in place of those the program holds
    (``control_correct``)."""
    limits = cell.numbers.get("limits", {})
    done = sample(finished, seed, int(cell.mix["check"]["sample"]))
    reqs = done + [s.req for s in snaps]
    fed = [r.out for r in done] + [s.out for s in snaps]
    lanes = list(range(len(done), len(reqs)))
    values = dict.fromkeys(NUMBERS + ("kv_rel_err",), float("inf"))
    ctrl = dict(values)
    pages = [list((chosen or {}).get(r.rid, [])) for r in reqs]
    if done and snaps and all(len(p) == len(f) for p, f in zip(pages, fed)):
        j = reference(cell, seed, reqs, fed, pages)
        values = _values(j, snaps, j.cache, lanes)
        log(f"check requests {len(reqs)} (in lanes {len(snaps)}) "
            f"tokens {sum(len(f) for f in fed)} "
            f"longest_prompt {max(len(r.prompt) for r in reqs)} "
            f"steps_with_slow_pages {sum(r.slow_steps for r in reqs)} "
            f"kv_rel_err {values['kv_rel_err']!r}")
        if control:
            c = reference(cell, seed, reqs, fed, kind=cell.model["control"])
            jc = reference(cell, seed, reqs, fed, c.chosen, judged=[c.picks])
            held = [Snapshot(s.req, s.out, c.cache[0][r, :, : s.k.shape[1]],
                             c.cache[1][r, :, : s.v.shape[1]]) for s, r in zip(snaps, lanes)]
            ctrl = _values(jc, held, j.cache, lanes)
    elif done and snaps:
        log("check: a sampled request lacks the pages of some step")
    correct, numbers = _verdict(values, limits)
    out = {"correct": correct}
    if control:
        out["control_correct"], more = _verdict(ctrl, limits, "control_")
        numbers.update(more)
    out["numbers"] = numbers
    return out
