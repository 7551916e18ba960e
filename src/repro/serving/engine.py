"""Serving engine: continuous batching over the tiered paged KV cache.

The decode data plane supplies exactly the access stream TPP consumes
(DESIGN.md §2):

* **Sliding-window layers** touch only the recent pages — old pages go
  cold naturally (gemma3's 5:1 pattern).
* **Page-level top-k sparse attention** (``topk_pages``): long-range
  layers attend the last ``recent_pages`` exactly plus the top-k older
  pages ranked by query·page-key-summary relevance (Quest/InfLLM-style,
  adapted to TPU whole-token-range pages).  This is the TPU-native
  source of the *page access skew* that CXL workloads exhibit in the
  paper (§3: 55-80% of pages idle over any 2-minute window); with
  ``topk_pages=None`` attention is exact/full and every page is hot
  (used by the parity tests).
* **Session pause/resume**: paused sequences' pages are retyped FILE and
  stop being touched → TPP demotes them; resume touches them again →
  promotion with hysteresis.

Two data planes (``EngineConfig.data_plane``, DESIGN.md §6):

* ``"reference"`` — one sequence at a time, per-layer Python loops,
  per-token cache writes.  Slow, obviously-correct executable spec.
* ``"batched"`` — all active sequences decode in **one jitted call**:
  per-step block tables feed ``kernels.paged_attention`` (grid
  ``(B, MP)``), token KV lands via batched scatters, page-key summaries
  live in an incrementally-updated device array, and migration payloads
  move in staged ``page_gather``/``page_scatter`` batches.  Identical
  greedy tokens and VmStat trajectories (tests/test_serving_parity.py).

Prefill runs the stack over a prompt as one jitted program
(``_prefill_program``), compiled once per padded prompt length: the next
power of two, at least one page.

Phase spans: the decode step, prefill and release are marked with
``jax.profiler.TraceAnnotation`` spans named ``serve.*`` (``serve.step``
around its flush, score, selection, tables, dispatch, policy and interval
phases; ``serve.prefill``; ``serve.finish``; a host read of a device
result is a ``*.wait`` span). A profiler records them on the clock of the
device planes; with none active each is a no-op of about a microsecond
of host CPU time.

The engine reports per-step slow-tier page hits to the policy
(`TppPolicy` or any baseline from ``repro.core.baselines``), which
migrates payloads through the cache's migration hook — real buffer
copies, identical mechanics to the kernel patchset, just one level down
the memory hierarchy.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import PageType, Tier, TppConfig, make_policy
from repro.kernels import ops as kernel_ops
from repro.qos import make_control
from repro.kernels.paged_attention import PAD_PAGE_POS
from repro.models import nn
from repro.models.attention import (AttnConfig, _rotate, make_cos_sin,
                                    reference_attention)
from repro.models.ffn import ffn_fwd
from repro.models.model import ModelConfig
from repro.models.moe import moe_fwd
from repro.serving.kv_cache import KVCacheConfig, TieredKVCache, bucket as _bucket


class AdmissionError(RuntimeError):
    """Raised when ``add_request`` refuses a request.

    ``reason`` distinguishes the cause: ``"max_seqs"`` (engine at its
    sequence cap — finish one first) vs ``"qos_pressure"`` (the tiering
    control plane is shedding batch-class load while the fast tier is
    under reclaim pressure; retry later or upgrade the class).
    """

    def __init__(self, message: str, reason: str = "max_seqs") -> None:
        super().__init__(message)
        self.reason = reason


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    page_size: int = 16
    num_fast: int = 256
    num_slow: int = 1024
    topk_pages: Optional[int] = 4  # None → exact full attention
    recent_pages: int = 2  # always-attended tail (exact local context)
    policy: str = "tpp"
    tpp: TppConfig = dataclasses.field(default_factory=TppConfig)
    max_seqs: int = 8
    data_plane: str = "reference"  # "reference" | "batched"
    # Multi-tenant QoS (repro.qos): a QosConfig arms the arbiter — or a
    # SlowdownControllerConfig the SLO feedback controller — as the KV
    # pool's TieringControl; requests are tagged with a tenant id +
    # priority class (``add_request``), defaulting to ``qos_class``.
    qos: Optional[Any] = None
    qos_class: str = "standard"
    # Shed batch-class admissions while the control plane reports
    # fast-tier pressure (``TieringControl.shed_batch_request``).
    admission_control: bool = True


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


class _Seq:
    """Engine-side sequence state."""

    def __init__(self, rid: int, tenant: int = 0,
                 qos_class: str = "standard") -> None:
        self.rid = rid
        self.tenant = tenant  # QoS tenant id (frame tagging)
        self.qos_class = qos_class
        self.pages: List[int] = []  # pids, in order
        self.cur_len = 0
        self.paused = False
        # prefilled but not yet inserted into a decode lane (the
        # continuous-batching front end's prefill/insert split) — a
        # detached sequence holds its KV but is skipped by step()
        self.detached = False


def _flat_layers(params: Any, cfg: ModelConfig) -> List[Any]:
    """Unstack scanned params → one param dict per layer, in order."""
    out: List[Any] = []
    for sp, (pat, reps) in zip(params["stacks"], cfg.stacks):
        for r in range(reps):
            for pos in range(len(pat)):
                blk = sp["blocks"][pos]
                if blk is None:
                    base = sp["shared"][pos]
                    lora = jax.tree_util.tree_map(lambda x: x[r], sp["lora"][pos])
                    out.append({"base": base, "lora": lora})
                else:
                    out.append(jax.tree_util.tree_map(lambda x: x[r], blk))
    return out


# Padded lengths traced by _prefill_program, one entry per trace (that is,
# per compile of a new program) in this process.
_prefill_traces: List[int] = []


@functools.partial(jax.jit, static_argnums=0)
def _prefill_program(cfg: ModelConfig, embed: Any, layers: List[Any],
                     toks: jax.Array, n_real: jax.Array
                     ) -> Tuple[jax.Array, jax.Array]:
    """One prompt through the stack → K and V of every layer, each
    ``(L, S_pad, Hkv, D)``.

    ``toks`` is the prompt padded at the end to ``S_pad``; the first
    ``n_real`` tokens are real.  Causal attention keeps the padding out of
    every real token's rows, and MoE layers keep the expert capacity of the
    real length, so the real rows are those of the unpadded forward.  The
    layer structure (``cfg``) is static and the weights are arguments, so
    engines of one configuration share one compile per padded length."""
    S = toks.shape[0]
    _prefill_traces.append(S)
    x = nn.embed(embed, toks[None, :])
    pos = jnp.arange(S, dtype=jnp.int32)[None, :]
    k_layers, v_layers = [], []
    for spec, p in zip(cfg.all_specs(), layers):
        pa = p["base"] if "base" in p else p
        a = spec.attn
        h = nn.rmsnorm(pa["norm1"], x)
        q = nn.dense(pa["attn"]["wq"], h).reshape(1, S, a.n_heads, a.head_dim)
        k = nn.dense(pa["attn"]["wk"], h).reshape(1, S, a.n_kv_heads, a.head_dim)
        v = nn.dense(pa["attn"]["wv"], h).reshape(1, S, a.n_kv_heads, a.head_dim)
        cos, sin = make_cos_sin(a, pos)
        if cos is not None:
            q = _rotate(a, q, cos, sin)
            k = _rotate(a, k, cos, sin)
        o = reference_attention(q, k, v, causal=True, window=a.window)
        y = nn.dense(pa["attn"]["wo"], o.reshape(1, S, -1))
        if "base" in p:
            lora = p["lora"]
            y = y + nn.dense({"w": lora["ob"]}, nn.dense({"w": lora["oa"]},
                nn.dense({"w": lora["qb"]}, nn.dense({"w": lora["qa"]}, h))))
        x = x + y
        if spec.has_ffn:
            h2 = nn.rmsnorm(pa["norm2"], x)
            if spec.moe is not None:
                y2, _ = moe_fwd(pa["moe"], spec.moe, h2, n_valid=n_real)
            else:
                y2 = ffn_fwd(pa["ffn"], h2, spec.ffn_kind)
            x = x + y2
        k_layers.append(k[0])  # (S, Hkv, D)
        v_layers.append(v[0])
    return jnp.stack(k_layers, axis=0), jnp.stack(v_layers, axis=0)


class ServingEngine:
    """Batched tiered-KV serving for attention-family architectures."""

    def __init__(
        self,
        cfg: ModelConfig,
        params: Any,
        engine: EngineConfig,
        seed: int = 0,
    ) -> None:
        for spec in cfg.all_specs():
            if spec.kind != "attn" or spec.attn.is_mla:
                raise ValueError(
                    "ServingEngine v1 pages GQA attention archs; SSM/hybrid "
                    "archs serve from O(1) recurrent state (TPP inapplicable; "
                    "see DESIGN.md §Arch-applicability), MLA via dense path"
                )
        if engine.data_plane not in ("reference", "batched"):
            raise ValueError(f"unknown data_plane {engine.data_plane!r}")
        if (engine.data_plane == "batched" and engine.topk_pages is not None
                and engine.recent_pages < 1):
            raise ValueError(
                "batched data plane needs recent_pages >= 1 with top-k "
                "attention (the decode-tail page must be block-table "
                "addressable)"
            )
        self.cfg = cfg
        self.ecfg = engine
        self.specs = cfg.all_specs()
        self.layers = _flat_layers(params, cfg)
        self.params = params
        a0 = self.specs[0].attn
        self.kv = TieredKVCache(
            KVCacheConfig(
                n_layers=cfg.n_layers,
                page_size=engine.page_size,
                n_kv_heads=a0.n_kv_heads,
                head_dim=a0.head_dim,
                num_fast=engine.num_fast,
                num_slow=engine.num_slow,
                staged_migration=(engine.data_plane == "batched"),
            ),
            tpp=engine.tpp,
        )
        # Any TieringControl (QosArbiter, SlowdownController, or a
        # telemetry-only TenantAccounting) — built via make_control.
        self.control = None
        if engine.qos is not None:
            self.control = make_control(
                engine.qos, n_tenants=1, fast_frames=engine.num_fast
            )
            self.kv.pool.control = self.control
        self.policy = make_policy(engine.policy, self.kv.pool, seed=seed)
        self.seqs: Dict[int, _Seq] = {}
        self.requests: Dict[int, Request] = {}
        self._next_rid = 0
        # page key summaries for top-k selection (reference plane):
        # pid -> (L, Hkv, D) np
        self._summaries: Dict[int, np.ndarray] = {}
        self.steps = 0
        # prefill programs compiled by this engine's calls (new padded
        # lengths; engines of one configuration share the programs)
        self.prefill_compiles = 0
        # per-step per-sequence tier hit split {rid: (fast, slow)} — the
        # traffic front end's latency model reads a lane's own residency
        # from here (refreshed by every step())
        self.last_hits: Dict[int, Tuple[int, int]] = {}
        # ------------------------------------------------------------ #
        # batched plane: per-slot device summary state + jitted fns
        # ------------------------------------------------------------ #
        self._slot_of: Dict[int, int] = {}
        self._free_slots = list(range(engine.max_seqs - 1, -1, -1))
        self._mp_cap = 8
        if engine.data_plane == "batched":
            L, Hkv, D = cfg.n_layers, a0.n_kv_heads, a0.head_dim
            # +1 trash slot: padded batch lanes accumulate there
            self._ksum = jnp.zeros(
                (engine.max_seqs + 1, self._mp_cap, L, Hkv, D), jnp.float32
            )
            self._kcnt = jnp.zeros(
                (engine.max_seqs + 1, self._mp_cap), jnp.float32
            )
            p0 = self.layers[0]
            pa0 = p0["base"] if "base" in p0 else p0
            self._probe_params = (params["embed"], pa0["norm1"],
                                  pa0["attn"]["wq"])
            self._step_fn = jax.jit(
                self._batched_step_impl, donate_argnums=(0, 1, 2, 3)
            )
            self._score_fn = jax.jit(self._score_impl)

    # ---------------------------------------------------------------- #
    # request lifecycle
    # ---------------------------------------------------------------- #
    def add_request(
        self,
        prompt: Sequence[int],
        max_new: int = 16,
        qos_class: Optional[str] = None,
        tenant: int = 0,
    ) -> int:
        """Admit a request; ``tenant``/``qos_class`` feed the QoS arbiter.

        ``tenant`` groups requests into one accounting/quota bucket (a
        stream of batch jobs can share one tenant id); ``qos_class``
        sets that tenant's priority class (default
        ``EngineConfig.qos_class``).  Ignored when QoS is off.

        With QoS armed, batch-class requests are **shed** (AdmissionError
        ``reason="qos_pressure"``) while the control plane reports
        fast-tier pressure — load drops before the fast tier thrashes
        the latency-critical tenants it is protecting.
        """
        if len(self.seqs) >= self.ecfg.max_seqs:
            raise AdmissionError(
                f"engine at max_seqs={self.ecfg.max_seqs}; finish() a "
                "sequence before admitting another",
                reason="max_seqs",
            )
        cls = qos_class or self.ecfg.qos_class
        if self.control is not None:
            if (self.ecfg.admission_control and cls == "batch"
                    and self.control.shed_batch_request(self.kv.pool)):
                raise AdmissionError(
                    "batch-class request shed: fast tier under reclaim "
                    "pressure with tenants over quota (control-plane "
                    "admission gate)",
                    reason="qos_pressure",
                )
            # validate/assign the class before any engine state mutates,
            # so a bad qos_class can't leave a zombie sequence behind
            self.control.configure_tenant(tenant, cls)
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid=rid, prompt=list(prompt), max_new=max_new)
        self.requests[rid] = req
        self.seqs[rid] = _Seq(rid, tenant=tenant, qos_class=cls)
        if self.ecfg.data_plane == "batched":
            self._slot_of[rid] = self._free_slots.pop()
        self._prefill(req)
        return rid

    # -------------------- continuous-batching lifecycle ------------- #
    def prefill_request(
        self,
        prompt: Sequence[int],
        max_new: int = 16,
        qos_class: Optional[str] = None,
        tenant: int = 0,
    ) -> int:
        """Admit + prefill a request *detached* from the decode batch.

        The JetStream-style ``prefill`` half of continuous batching: the
        prompt's KV lands in the tiered cache (generating the same
        allocation pressure a running sequence would) but ``step()``
        skips the sequence until :meth:`insert_request` attaches it to a
        decode lane.  Admission (``max_seqs`` cap, batch-class QoS
        shedding) is identical to :meth:`add_request`.
        """
        rid = self.add_request(
            prompt, max_new=max_new, qos_class=qos_class, tenant=tenant
        )
        self.seqs[rid].detached = True
        return rid

    def insert_request(self, rid: int) -> None:
        """Attach a prefilled (detached) sequence to the decode batch."""
        seq = self.seqs[rid]
        if not seq.detached:
            raise ValueError(
                f"request {rid} is already inserted into the decode batch"
            )
        seq.detached = False

    def free_lanes(self) -> int:
        """Decode lanes still unclaimed (``max_seqs`` minus live seqs)."""
        return self.ecfg.max_seqs - len(self.seqs)

    def pause(self, rid: int) -> None:
        """Session pause: pages become FILE (cold prefix bulk, §5.4)."""
        seq = self.seqs[rid]
        seq.paused = True
        for pid in seq.pages:
            self.kv.retype(pid, PageType.FILE)

    def resume(self, rid: int) -> None:
        seq = self.seqs[rid]
        seq.paused = False
        if seq.pages:
            # The still-being-written tail resumes as the hot decode page;
            # without this it would stay FILE forever and §5.4 type-aware
            # allocation would misclassify every subsequent write.
            self.kv.retype(seq.pages[-1], PageType.ANON)

    def finish(self, rid: int) -> Request:
        """Release a sequence; returns its (now detached) Request."""
        with TraceAnnotation("serve.finish", rid=rid):
            seq = self.seqs.pop(rid)
            for pid in seq.pages:
                self._summaries.pop(pid, None)
                self.kv.free_page(pid)
            req = self.requests.pop(rid)
            if self.ecfg.data_plane == "batched":
                slot = self._slot_of.pop(rid)
                self._ksum = self._ksum.at[slot].set(0.0)
                self._kcnt = self._kcnt.at[slot].set(0.0)
                self._free_slots.append(slot)
        return req

    # ---------------------------------------------------------------- #
    # prefill
    # ---------------------------------------------------------------- #
    def _ensure_page(self, seq: _Seq) -> Tuple[int, int]:
        """Page + slot for the next token; allocates on boundary."""
        slot = seq.cur_len % self.ecfg.page_size
        if slot == 0:
            if seq.pages:
                # the sealed tail page becomes long-lived prefix bulk
                self.kv.retype(seq.pages[-1], PageType.FILE)
            seq.pages.append(
                self.kv.alloc_page(PageType.ANON, tenant=seq.tenant)
            )
        return seq.pages[-1], slot

    def _prefill_forward(self, req: Request,
                         padded: int) -> Tuple[jax.Array, jax.Array]:
        """Run the stack over ``prompt[:-1]`` → per-layer K and V.

        Returns ``(k_all, v_all)`` of shape ``(L, S, Hkv, D)``.  The last
        prompt token is fed by the first decode step (whose logits
        produce the first generated token) — standard prefill/decode
        split.  The tokens are padded at the end to ``padded`` and run
        through :func:`_prefill_program`, compiled once per padded
        length; ``prefill_compiles`` counts the compiles this engine's
        calls caused."""
        S = len(req.prompt) - 1
        toks = np.zeros(padded, np.int32)
        toks[:S] = req.prompt[:-1]
        traced = len(_prefill_traces)
        k_all, v_all = _prefill_program(
            self.cfg, self.params["embed"], self.layers, jnp.asarray(toks), S)
        self.prefill_compiles += len(_prefill_traces) - traced
        return k_all[:, :S], v_all[:, :S]

    def _prefill(self, req: Request) -> None:
        seq = self.seqs[req.rid]
        if len(req.prompt) <= 1:
            return
        # the next power of two, at least one page
        padded = max(_bucket(len(req.prompt) - 1), self.ecfg.page_size)
        with TraceAnnotation("serve.prefill", rid=req.rid, tokens=len(req.prompt),
                             bucket=padded):
            with TraceAnnotation("serve.prefill.forward"):
                k_all, v_all = self._prefill_forward(req, padded)  # (L, S, Hkv, D)
            with TraceAnnotation("serve.prefill.write"):
                if self.ecfg.data_plane == "batched":
                    self._prefill_write_batched(seq, k_all, v_all)
                else:
                    self._prefill_write_reference(seq, k_all, v_all)

    def _prefill_write_reference(self, seq: _Seq, k_all: jax.Array,
                                 v_all: jax.Array) -> None:
        """Land the prompt KV one token at a time (the reference plane)."""
        L, S = k_all.shape[0], k_all.shape[1]
        kv_all = jnp.concatenate(
            [k_all.reshape(L, S, -1), v_all.reshape(L, S, -1)], axis=-1
        )  # (L, S, W) — layout [all-k | all-v]
        for t in range(S):
            pid, slot = self._ensure_page(seq)
            self.kv.write_token(pid, slot, kv_all[:, t, :])
            seq.cur_len += 1
        self._refresh_summaries(seq)

    def _prefill_write_batched(self, seq: _Seq, k_all: jax.Array,
                               v_all: jax.Array) -> None:
        """Land the whole prompt KV in one scatter per store and seed the
        per-page key-summary device arrays."""
        P = self.ecfg.page_size
        L, S = k_all.shape[0], k_all.shape[1]
        pids, slots = [], []
        for _ in range(S):
            pid, slot = self._ensure_page(seq)
            pids.append(pid)
            slots.append(slot)
            seq.cur_len += 1
        self.kv.write_tokens(
            pids, slots, jnp.moveaxis(k_all, 1, 0), jnp.moveaxis(v_all, 1, 0)
        )
        npages = len(seq.pages)
        self._grow_summaries(npages)
        pad = npages * P - S
        kp = jnp.pad(k_all.astype(jnp.float32), ((0, 0), (0, pad), (0, 0), (0, 0)))
        sums = kp.reshape(L, npages, P, kp.shape[2], kp.shape[3]).sum(axis=2)
        sums = jnp.moveaxis(sums, 0, 1)  # (npages, L, Hkv, D)
        counts = np.full(npages, P, np.float32)
        counts[-1] = P - pad
        slot_id = self._slot_of[seq.rid]
        self._ksum = self._ksum.at[slot_id, :npages].set(sums)
        self._kcnt = self._kcnt.at[slot_id, :npages].set(jnp.asarray(counts))

    def _refresh_summaries(self, seq: _Seq) -> None:
        a0 = self.specs[0].attn
        Hkv, D = a0.n_kv_heads, a0.head_dim
        for pid in seq.pages:
            page = np.asarray(self.kv.gather_pages([pid])[0])  # (L, P, W)
            k = page[..., : Hkv * D].reshape(page.shape[0], page.shape[1], Hkv, D)
            self._summaries[pid] = k.mean(axis=1)  # (L, Hkv, D)

    def _grow_summaries(self, needed: int) -> None:
        if self.ecfg.data_plane != "batched" or needed <= self._mp_cap:
            return
        new_cap = _bucket(needed)
        pad = new_cap - self._mp_cap
        self._ksum = jnp.pad(self._ksum, ((0, 0), (0, pad)) + ((0, 0),) * 3)
        self._kcnt = jnp.pad(self._kcnt, ((0, 0), (0, pad)))
        self._mp_cap = new_cap

    # ---------------------------------------------------------------- #
    # page selection (the access skew)
    # ---------------------------------------------------------------- #
    def _select_pages(self, seq: _Seq, older_scores: np.ndarray) -> List[int]:
        """Recent tail pages (exact) + top-k older pages by relevance.

        ``older_scores[i]`` scores ``seq.pages[i]`` for the non-recent
        prefix; both planes produce it from the same page-key summaries
        (host dict vs device array)."""
        n = len(seq.pages)
        recent = seq.pages[max(0, n - self.ecfg.recent_pages):]
        if self.ecfg.topk_pages is None:
            return list(seq.pages)
        older = seq.pages[: max(0, n - self.ecfg.recent_pages)]
        if not older or self.ecfg.topk_pages == 0:
            return recent
        order = np.argsort(older_scores)[::-1][: self.ecfg.topk_pages]
        return [older[i] for i in sorted(order)] + recent

    # ---------------------------------------------------------------- #
    # decode
    # ---------------------------------------------------------------- #
    def step(self) -> Dict[int, int]:
        """One decode step for all active sequences → {rid: token}."""
        active = [s for s in self.seqs.values()
                  if not s.paused and not s.detached
                  and not self.requests[s.rid].done]
        with TraceAnnotation("serve.step", lanes=len(active)):
            self.last_hits = {}
            if self.ecfg.data_plane == "batched":
                out, slow_hits, fast_hits = self._decode_batched(active)
            else:
                out = {}
                slow_hits, fast_hits = [], []
                for seq in active:
                    tok, s_hits, f_hits = self._decode_one(seq)
                    out[seq.rid] = tok
                    slow_hits += s_hits
                    fast_hits += f_hits
                    self.last_hits[seq.rid] = (len(f_hits), len(s_hits))
            for rid, tok in out.items():
                req = self.requests[rid]
                req.out.append(tok)
                if len(req.out) >= req.max_new:
                    req.done = True
            with TraceAnnotation("serve.policy"):
                if self.control is not None:
                    # per-tenant hotness + slowdown telemetry (tier-split feeds
                    # the slowdown controller's measured per-tenant slowdown)
                    self.control.note_hits(
                        np.fromiter(fast_hits, np.int64, count=len(fast_hits)),
                        np.fromiter(slow_hits, np.int64, count=len(slow_hits)),
                    )
                # Uniform PlacementPolicy protocol: every policy receives both hit
                # streams (NUMA balancing samples fast hits; the rest ignore them).
                self.policy.step(slow_hits, fast_hits)
            self.steps += 1
            if self.steps % 4 == 0:
                with TraceAnnotation("serve.interval"):
                    self.kv.pool.end_interval()  # also ticks control.note_interval
        return out

    # ------------------------- reference plane ---------------------- #
    def _decode_one(self, seq: _Seq) -> Tuple[int, List[int], List[int]]:
        req = self.requests[seq.rid]
        last_tok = (req.out[-1] if req.out else req.prompt[-1])
        t = seq.cur_len  # position of the new token
        x = nn.embed(self.params["embed"], jnp.asarray([[last_tok]], jnp.int32))
        pos = jnp.asarray([[t]], jnp.int32)

        # page selection is shared across layers (pages span all layers);
        # use the embedding-projected mean query of layer 0 as the probe.
        a0 = self.specs[0].attn
        p0 = self.layers[0]["base"] if "base" in self.layers[0] else self.layers[0]
        q_probe = nn.dense(p0["attn"]["wq"], nn.rmsnorm(p0["norm1"], x))
        q_mean = np.asarray(
            q_probe.reshape(a0.n_heads, a0.head_dim)
            .reshape(a0.n_kv_heads, -1, a0.head_dim)
            .mean(axis=1)
        )  # (Hkv, D)
        older = seq.pages[: max(0, len(seq.pages) - self.ecfg.recent_pages)]
        older_scores = np.asarray([
            float(np.einsum("hd,lhd->", q_mean, self._summaries[pid]))
            if pid in self._summaries else -1e9
            for pid in older
        ])
        sel = self._select_pages(seq, older_scores)

        # touch + tier accounting (the TPP access stream)
        s_hits, f_hits = [], []
        for pid in sel:
            tier = self.kv.pool.touch(pid)
            (s_hits if tier == Tier.SLOW else f_hits).append(pid)

        pages = self.kv.gather_pages(sel)  # (n, L, P, W)
        n_sel = len(sel)
        P = self.ecfg.page_size
        # valid token count per selected page
        valid = np.zeros((n_sel, P), dtype=bool)
        page_index = {pid: i for i, pid in enumerate(seq.pages)}
        for j, pid in enumerate(sel):
            gi = page_index[pid]
            start = gi * P
            valid[j] = (np.arange(P) + start) < t
        valid_j = jnp.asarray(valid.reshape(-1))

        kv_new_layers = []
        for li, spec in enumerate(self.specs):
            p = self.layers[li]
            pa = p["base"] if "base" in p else p
            a = spec.attn
            h = nn.rmsnorm(pa["norm1"], x)
            q = nn.dense(pa["attn"]["wq"], h).reshape(1, 1, a.n_heads, a.head_dim)
            k = nn.dense(pa["attn"]["wk"], h).reshape(1, 1, a.n_kv_heads, a.head_dim)
            v = nn.dense(pa["attn"]["wv"], h).reshape(1, 1, a.n_kv_heads, a.head_dim)
            cos, sin = make_cos_sin(a, pos)
            if cos is not None:
                q = _rotate(a, q, cos, sin)
                k = _rotate(a, k, cos, sin)

            Hkv, D = a.n_kv_heads, a.head_dim
            # explicit width: -1 is uninferable when sel is empty (first
            # decode of a single-token prompt)
            lay = pages[:, li].reshape(n_sel * P, pages.shape[-1])  # (nP, W)
            ks = lay[:, : Hkv * D].reshape(-1, Hkv, D)
            vs = lay[:, Hkv * D :].reshape(-1, Hkv, D)
            ks = jnp.concatenate([ks, k[0, :, :, :]], axis=0)  # append current
            vs = jnp.concatenate([vs, v[0, :, :, :]], axis=0)
            vmask = jnp.concatenate([valid_j, jnp.ones((1,), bool)])
            if a.window is not None:
                # window mask by absolute position of each cache slot
                abs_pos = np.concatenate(
                    [np.arange(P) + page_index[pid] * P for pid in sel] + [[t]]
                )
                vmask &= jnp.asarray(abs_pos > t - a.window)

            G = a.n_heads // Hkv
            qg = q[0, 0].reshape(Hkv, G, D) / math.sqrt(D)
            s = jnp.einsum("hgd,thd->hgt", qg.astype(jnp.float32), ks.astype(jnp.float32))
            s = jnp.where(vmask[None, None, :], s, -jnp.inf)
            pr = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("hgt,thd->hgd", pr, vs.astype(jnp.float32))
            y = nn.dense(pa["attn"]["wo"], o.reshape(1, 1, -1).astype(x.dtype))
            if "base" in p:
                lora = p["lora"]
                y = y + nn.dense({"w": lora["ob"]}, nn.dense({"w": lora["oa"]},
                    nn.dense({"w": lora["qb"]}, nn.dense({"w": lora["qa"]}, h))))
            x = x + y
            if spec.has_ffn:
                h2 = nn.rmsnorm(pa["norm2"], x)
                if spec.moe is not None:
                    y2, _ = moe_fwd(pa["moe"], spec.moe, h2)
                else:
                    y2 = ffn_fwd(pa["ffn"], h2, spec.ffn_kind)
                x = x + y2
            kv_new_layers.append(
                jnp.concatenate([k[0, 0].reshape(-1), v[0, 0].reshape(-1)])
            )

        h = nn.rmsnorm(self.params["final_norm"], x)
        if self.cfg.tie_embeddings:
            logits = h @ self.params["embed"]["table"].T.astype(h.dtype)
        else:
            logits = nn.dense(self.params["lm_head"], h)
        tok = int(jnp.argmax(logits[0, -1]))

        # write the new token's KV and update summaries for its page
        pid, slot = self._ensure_page(seq)
        self.kv.write_token(pid, slot, jnp.stack(kv_new_layers))
        seq.cur_len += 1
        page = np.asarray(self.kv.gather_pages([pid])[0])
        a0 = self.specs[0].attn
        kk = page[:, : slot + 1, : a0.n_kv_heads * a0.head_dim].reshape(
            len(self.specs), slot + 1, a0.n_kv_heads, a0.head_dim
        )
        self._summaries[pid] = kk.mean(axis=1)
        return tok, s_hits, f_hits

    # ------------------------- batched plane ------------------------ #
    def _decode_batched(
        self, active: List[_Seq]
    ) -> Tuple[Dict[int, int], List[int], List[int]]:
        """One decode step for all active sequences in one jitted call."""
        if not active:
            return {}, [], []
        self.kv.flush_migrations()
        ecfg = self.ecfg
        P = ecfg.page_size
        B = len(active)
        toks = np.zeros(B, np.int32)
        for b, seq in enumerate(active):
            req = self.requests[seq.rid]
            toks[b] = req.out[-1] if req.out else req.prompt[-1]

        # top-k relevance scores from the device summary arrays (one
        # small transfer per step — no per-page gather round-trips)
        scores = None
        if (ecfg.topk_pages not in (None, 0)
                and any(len(s.pages) > ecfg.recent_pages for s in active)):
            with TraceAnnotation("serve.score"):
                slot_ids = jnp.asarray(
                    [self._slot_of[s.rid] for s in active], jnp.int32
                )
                scores = self._score_fn(
                    self._probe_params, self._ksum, self._kcnt,
                    jnp.asarray(toks), slot_ids,
                )
            with TraceAnnotation("serve.score.wait"):
                scores = np.asarray(scores)

        # selection + touch/tier accounting, in sequence order (the same
        # access stream the reference plane emits)
        sels: List[List[int]] = []
        s_hits: List[int] = []
        f_hits: List[int] = []
        with TraceAnnotation("serve.select") as span:
            for b, seq in enumerate(active):
                n_older = max(0, len(seq.pages) - ecfg.recent_pages)
                older_scores = (scores[b, :n_older] if scores is not None
                                else np.zeros(n_older, np.float32))
                sel = self._select_pages(seq, older_scores)
                sels.append(sel)
                nf = ns = 0
                for pid in sel:
                    tier = self.kv.pool.touch(pid)
                    if tier == Tier.SLOW:
                        s_hits.append(pid)
                        ns += 1
                    else:
                        f_hits.append(pid)
                        nf += 1
                self.last_hits[seq.rid] = (nf, ns)
            span.set_metadata(fast=len(f_hits), slow=len(s_hits))

        with TraceAnnotation("serve.tables") as span:
            # allocate every sequence's write target (page-boundary allocs
            # land here; touch order above matches the reference plane —
            # touches never move frames, so the interleave is immaterial)
            writes = [self._ensure_page(seq) for seq in active]
            self._grow_summaries(max(len(s.pages) for s in active))

            # per-step block tables: selected pages (+ the write page when a
            # boundary alloc created it after selection), padded to buckets
            entries = []
            for b, seq in enumerate(active):
                ent = list(sels[b])
                if writes[b][0] not in ent:
                    ent.append(writes[b][0])
                entries.append(ent)
            Bp = _bucket(B)
            MPp = _bucket(max(len(e) for e in entries))
            trash = self.kv.trash_frame
            bt = np.full((Bp, MPp), trash, np.int32)
            ps = np.full((Bp, MPp), PAD_PAGE_POS, np.int32)
            qpos = np.zeros(Bp, np.int32)
            wframe = np.full(Bp, trash, np.int32)
            wslot = np.zeros(Bp, np.int32)
            slot_arr = np.full(Bp, ecfg.max_seqs, np.int32)
            gi_arr = np.zeros(Bp, np.int32)
            toks_in = np.zeros(Bp, np.int32)
            for b, seq in enumerate(active):
                page_index = {pid: i for i, pid in enumerate(seq.pages)}
                for j, pid in enumerate(entries[b]):
                    bt[b, j] = self.kv.global_frame(pid)
                    ps[b, j] = page_index[pid] * P
                qpos[b] = seq.cur_len
                wframe[b] = self.kv.global_frame(writes[b][0])
                wslot[b] = writes[b][1]
                slot_arr[b] = self._slot_of[seq.rid]
                gi_arr[b] = len(seq.pages) - 1
                toks_in[b] = toks[b]
            span.set_metadata(bp=Bp, mpp=MPp)

        with TraceAnnotation("serve.dispatch"):
            out_toks, self.kv.k_store, self.kv.v_store, self._ksum, self._kcnt = (
                self._step_fn(
                    self.kv.k_store, self.kv.v_store, self._ksum, self._kcnt,
                    self.params, self.layers,
                    jnp.asarray(toks_in), jnp.asarray(qpos),
                    jnp.asarray(bt), jnp.asarray(ps),
                    jnp.asarray(wframe), jnp.asarray(wslot),
                    jnp.asarray(slot_arr), jnp.asarray(gi_arr),
                )
            )
        with TraceAnnotation("serve.tokens.wait"):
            out_toks = np.asarray(out_toks)
        out: Dict[int, int] = {}
        for b, seq in enumerate(active):
            seq.cur_len += 1
            out[seq.rid] = int(out_toks[b])
        return out, s_hits, f_hits

    def _batched_step_impl(
        self, k_store, v_store, ksum, kcnt, params, layers,
        toks, q_pos, block_table, page_start, wframe, wslot, slot_ids, gi,
    ):
        """The jitted batched decode step: token writes as batched
        scatters, attention via ``kernels.paged_attention`` per layer."""
        B = toks.shape[0]
        x = nn.embed(params["embed"], toks[:, None])  # (B, 1, d)
        pos = q_pos[:, None]
        k_layers = []
        for li, spec in enumerate(self.specs):
            p = layers[li]
            pa = p["base"] if "base" in p else p
            a = spec.attn
            h = nn.rmsnorm(pa["norm1"], x)
            q = nn.dense(pa["attn"]["wq"], h).reshape(B, 1, a.n_heads, a.head_dim)
            k = nn.dense(pa["attn"]["wk"], h).reshape(B, 1, a.n_kv_heads, a.head_dim)
            v = nn.dense(pa["attn"]["wv"], h).reshape(B, 1, a.n_kv_heads, a.head_dim)
            cos, sin = make_cos_sin(a, pos)
            if cos is not None:
                q = _rotate(a, q, cos, sin)
                k = _rotate(a, k, cos, sin)
            k_t, v_t = k[:, 0], v[:, 0]  # (B, Hkv, D)
            # land the step's token KV (in-program batched scatter)
            k_store = k_store.at[wframe, li, :, wslot, :].set(
                k_t.astype(k_store.dtype))
            v_store = v_store.at[wframe, li, :, wslot, :].set(
                v_t.astype(v_store.dtype))
            o = kernel_ops.paged_attention(
                q[:, 0], k_store[:, li], v_store[:, li], block_table,
                page_pos=page_start, q_pos=q_pos, window=a.window,
            )  # (B, H, D)
            y = nn.dense(pa["attn"]["wo"], o.reshape(B, 1, -1).astype(x.dtype))
            if "base" in p:
                lora = p["lora"]
                y = y + nn.dense({"w": lora["ob"]}, nn.dense({"w": lora["oa"]},
                    nn.dense({"w": lora["qb"]}, nn.dense({"w": lora["qa"]}, h))))
            x = x + y
            if spec.has_ffn:
                h2 = nn.rmsnorm(pa["norm2"], x)
                if spec.moe is not None:
                    y2, _ = moe_fwd(pa["moe"], spec.moe, h2)
                else:
                    y2 = ffn_fwd(pa["ffn"], h2, spec.ffn_kind)
                x = x + y2
            k_layers.append(k_t)
        h = nn.rmsnorm(params["final_norm"], x)
        if self.cfg.tie_embeddings:
            logits = h @ params["embed"]["table"].T.astype(h.dtype)
        else:
            logits = nn.dense(params["lm_head"], h)
        toks_out = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        # incremental page-key summaries (padded lanes hit the trash slot)
        k_all = jnp.stack(k_layers, axis=1).astype(jnp.float32)  # (B,L,Hkv,D)
        ksum = ksum.at[slot_ids, gi].add(k_all)
        kcnt = kcnt.at[slot_ids, gi].add(1.0)
        return toks_out, k_store, v_store, ksum, kcnt

    def _score_impl(self, probe_params, ksum, kcnt, toks, slot_ids):
        """Query·page-key-summary relevance for every (seq, page)."""
        embed_p, norm1_p, wq_p = probe_params
        a0 = self.specs[0].attn
        B = toks.shape[0]
        x = nn.embed(embed_p, toks[:, None])
        qp = nn.dense(wq_p, nn.rmsnorm(norm1_p, x))
        qm = qp.reshape(B, a0.n_kv_heads, -1, a0.head_dim).mean(axis=2)
        means = ksum[slot_ids] / jnp.maximum(
            kcnt[slot_ids], 1.0)[:, :, None, None, None]
        return jnp.einsum("bhd,bmlhd->bm", qm.astype(jnp.float32), means)

    # ---------------------------------------------------------------- #
    def as_shard_pool(self, host: int = 0, name: str = "kv", slo=None,
                      traffic=None):
        """Register this engine's KV pool as a fleet shard.

        The returned :class:`~repro.fleet.shard.ShardPool` lets a
        :class:`~repro.fleet.coordinator.FleetCoordinator` budget the
        KV cache's fast tier alongside other pools on the same host —
        push-downs land through ``pool.set_fast_budget``, telemetry
        windows come from the engine's attached control ledger (a
        control-free engine reports on-target).  ``traffic`` optionally
        attaches a :class:`~repro.traffic.scheduler.TrafficScheduler`
        over this engine so ``HostShard.step`` drives the shard from a
        request trace.  Import is lazy so serving stays usable without
        the fleet package.
        """
        from repro.fleet.shard import ShardPool

        return ShardPool(
            host=host, name=name, pool=self.kv.pool,
            control=self.control, slo=slo, traffic=traffic,
        )

    def stats(self) -> Dict[str, Any]:
        vs = self.kv.pool.vmstat
        out = {
            "steps": self.steps,
            "local_fraction": vs.local_access_fraction,
            "demoted": vs.pgdemote_total,
            "promoted": vs.pgpromote_total,
            "migrated_bytes": self.kv.migrated_bytes,
            "fast_free": self.kv.pool.free_frames(Tier.FAST),
            "slow_used": self.kv.pool.used_frames(Tier.SLOW),
        }
        if self.control is not None:
            out["qos"] = self.control.qos_summary()
        return out
