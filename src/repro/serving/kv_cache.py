"""Two-tier paged KV cache — the serving-side embodiment of TPP.

Mapping onto the paper (DESIGN.md §2, §6):

* **page**   = ``page_size`` tokens × all attention layers of one
  sequence (the migration unit, like an OS page spanning an address
  range).
* **frame space** — one global frame index range, split by tier exactly
  like the paper's single physical address space spanning both NUMA
  nodes: frames ``[0, num_fast)`` are the fast tier (HBM on a real
  mesh), frames ``[num_fast, num_fast+num_slow)`` the slow tier
  (``memory_kind='pinned_host'`` / CXL).  CXL memory is load/store
  addressable, so the decode path may read slow frames in place — it is
  just slower, which is precisely the access asymmetry TPP manages.
* **payload layout** — kernel-native split K/V stores
  ``(F, L, Hkv, P, D)``: frame-major so one ``page_gather`` /
  ``page_scatter`` moves a whole page across tiers, with per-layer
  slices ``store[:, li]`` feeding ``kernels.paged_attention`` directly.
* The **PagePool** from ``repro.core`` is the metadata manager: the
  engine reports page touches, TPP (or a baseline policy) decides
  migrations, and this class executes the payload copies.

With ``staged_migration=True`` (the batched data plane) the copies of
one policy interval are *staged* and executed as one
``page_gather``→``page_scatter`` pair per direction at the next payload
access — the §5.1 "migration never stalls the access path" behaviour.
With ``staged_migration=False`` every migration copies eagerly (the
executable reference).  Both produce identical payloads.

Page types: decode-active tail pages of running sequences are ANON
(hot, short-lived); full prefix pages and pages of paused sessions are
FILE (bulky, re-accessed on resume / by sparse long-range attention) —
the §5.4 type-aware allocation then steers prefix bulk to the slow tier
under pressure.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.analysis.plan_verify import CopyOp, check_plan, plan_from_staged
from repro.core import PagePool, PageType, Tier, TppConfig
from repro.kernels import ops as kernel_ops


def bucket(n: int) -> int:
    """Next power of two ≥ n — pads batch shapes to a few stable buckets
    so jit caches (decode step, staged-copy kernels) never churn."""
    b = 1
    while b < n:
        b <<= 1
    return b


@dataclasses.dataclass(frozen=True)
class KVCacheConfig:
    n_layers: int
    page_size: int  # tokens per page
    n_kv_heads: int
    head_dim: int
    num_fast: int  # frames in the fast tier
    num_slow: int
    dtype: str = "float32"
    # Batch one policy interval's payload copies into a single staged
    # gather/scatter per direction (the batched data plane); False
    # copies eagerly per page (the executable reference).
    staged_migration: bool = False

    @property
    def kv_width(self) -> int:
        """Per-token-per-layer elements: k‖v packed (2·Hkv·D)."""
        return 2 * self.n_kv_heads * self.head_dim

    @property
    def page_bytes(self) -> int:
        itemsize = jnp.dtype(self.dtype).itemsize
        return self.n_layers * self.page_size * self.kv_width * itemsize


@dataclasses.dataclass
class _StagedCopy:
    pid: int
    src: int  # global frame
    dst: int  # global frame
    demote: bool  # fast→slow


class TieredKVCache:
    """Physical two-tier paged KV store + logical page table."""

    def __init__(self, cfg: KVCacheConfig, tpp: Optional[TppConfig] = None) -> None:
        self.cfg = cfg
        dt = jnp.dtype(cfg.dtype)
        self.num_slow = max(cfg.num_slow, 1)
        # +1 trash frame: padded lanes of batched writes land there.
        total = cfg.num_fast + self.num_slow + 1
        self.trash_frame = total - 1
        shape = (total, cfg.n_layers, cfg.n_kv_heads, cfg.page_size, cfg.head_dim)
        self.k_store = jnp.zeros(shape, dt)
        self.v_store = jnp.zeros(shape, dt)
        self.pool = PagePool(
            cfg.num_fast, cfg.num_slow, config=tpp,
            on_migrate=self._on_migrate, on_evict=self._cancel_pending,
        )
        self.migrated_pages = 0
        self.migrated_bytes = 0
        self._pending: List[_StagedCopy] = []
        self._pending_src: set = set()
        self._pending_dst: set = set()
        # Debug-build plan verification (TIERSAN_PLAN_CHECK=1): every
        # flushed migration batch is checked for frame hazards under the
        # gathers-first staging the kernels execute, and the last plan is
        # kept for offline triage (repro.analysis.plan_verify).
        self.plan_check = (
            os.environ.get("TIERSAN_PLAN_CHECK", "") not in ("", "0")
        )
        self.last_plan: Optional[List[CopyOp]] = None
        # one shared staged-copy width → one compiled gather/scatter
        # shape for the whole engine lifetime.  Sized from the policy
        # budgets (an interval batch can't exceed them) and prewarmed so
        # no flush ever pays a jit compile on the serving path.
        self._flush_width = 1
        if cfg.staged_migration:
            self._flush_width = bucket(max(self.pool.config.demote_budget,
                                           self.pool.config.promote_budget, 1))
            idx = jnp.full((self._flush_width,), self.trash_frame, jnp.int32)
            self.k_store = kernel_ops.page_scatter(
                self.k_store, idx, kernel_ops.page_gather(self.k_store, idx))
            self.v_store = kernel_ops.page_scatter(
                self.v_store, idx, kernel_ops.page_gather(self.v_store, idx))

    # ---------------------------------------------------------------- #
    # frame addressing
    # ---------------------------------------------------------------- #
    def _global(self, tier: Tier, frame: int) -> int:
        return frame if tier == Tier.FAST else self.cfg.num_fast + frame

    def global_frame(self, pid: int) -> int:
        """Global frame index of a page (fast tier first, then slow)."""
        page = self.pool.pages[pid]
        return self._global(page.tier, page.frame)

    def global_frames(self, pids: Sequence[int]) -> np.ndarray:
        return np.fromiter(
            (self.global_frame(int(p)) for p in pids), np.int32, count=len(pids)
        )

    # ---------------------------------------------------------------- #
    # migration data plane
    # ---------------------------------------------------------------- #
    def _on_migrate(self, pid: int, src: Tier, src_frame: int, dst: Tier,
                    dst_frame: int) -> None:
        """PagePool hook: copy (or stage) one page between tiers."""
        src_g = self._global(src, src_frame)
        dst_g = self._global(dst, dst_frame)
        self.migrated_pages += 1
        self.migrated_bytes += self.cfg.page_bytes
        if not self.cfg.staged_migration:
            self.k_store = self.k_store.at[dst_g].set(self.k_store[src_g])
            self.v_store = self.v_store.at[dst_g].set(self.v_store[src_g])
            return
        if src_g in self._pending_dst:
            # chained move (the page migrated earlier this interval and
            # its payload has not landed yet) — settle the batch first.
            self.flush_migrations()
        self._pending.append(
            _StagedCopy(pid=pid, src=src_g, dst=dst_g, demote=(src == Tier.FAST))
        )
        self._pending_src.add(src_g)
        self._pending_dst.add(dst_g)

    def _cancel_pending(self, pid: int) -> None:
        """Drop staged copies of a page that is being freed/evicted."""
        if not self._pending:
            return
        self._pending = [c for c in self._pending if c.pid != pid]
        self._pending_src = {c.src for c in self._pending}
        self._pending_dst = {c.dst for c in self._pending}

    def flush_migrations(self) -> None:
        """Execute the staged interval batch: one ``page_gather`` →
        ``page_scatter`` per direction per store.

        All gathers run before any scatter, so a frame freed by a
        demotion and immediately reclaimed by a promotion (or vice
        versa) still sources the pre-interval payload.
        """
        if not self._pending:
            return
        pending, self._pending = self._pending, []
        self._pending_src, self._pending_dst = set(), set()
        n_demote = sum(c.demote for c in pending)
        with TraceAnnotation("serve.flush", demote=n_demote,
                             promote=len(pending) - n_demote):
            if self.plan_check:
                self.last_plan = plan_from_staged(pending)
                check_plan(
                    self.last_plan,
                    num_frames=self.trash_frame + 1,
                    trash_frame=self.trash_frame,
                    staging="gathers-first",
                )
            # pad every batch to one shared power-of-two width via the trash
            # frame (a self-copy of garbage): batch-size jitter then never
            # forces a gather/scatter recompile
            self._flush_width = max(self._flush_width, bucket(max(
                n_demote, len(pending) - n_demote)))
            batches = []  # (dst_frames, staged_k, staged_v) — gather phase
            for demote in (True, False):
                group = [c for c in pending if c.demote == demote]
                if not group:
                    continue
                pad = [self.trash_frame] * (self._flush_width - len(group))
                src = jnp.asarray([c.src for c in group] + pad, jnp.int32)
                dst = jnp.asarray([c.dst for c in group] + pad, jnp.int32)
                batches.append((
                    dst,
                    kernel_ops.page_gather(self.k_store, src),
                    kernel_ops.page_gather(self.v_store, src),
                ))
            for dst, staged_k, staged_v in batches:  # scatter phase
                self.k_store = kernel_ops.page_scatter(self.k_store, dst, staged_k)
                self.v_store = kernel_ops.page_scatter(self.v_store, dst, staged_v)

    def _flush_if_touches(self, gframe: int) -> None:
        if self._pending and (
            gframe in self._pending_src or gframe in self._pending_dst
        ):
            self.flush_migrations()

    # ---------------------------------------------------------------- #
    # payload plumbing
    # ---------------------------------------------------------------- #
    def write_token(self, pid: int, slot: int, kv: jax.Array) -> None:
        """Write one token's KV ``(L, W)`` into page ``pid`` at ``slot``."""
        gf = self.global_frame(pid)
        self._flush_if_touches(gf)
        L, Hkv, D = self.cfg.n_layers, self.cfg.n_kv_heads, self.cfg.head_dim
        k = kv[:, : Hkv * D].reshape(L, Hkv, D).astype(self.k_store.dtype)
        v = kv[:, Hkv * D:].reshape(L, Hkv, D).astype(self.v_store.dtype)
        self.k_store = self.k_store.at[gf, :, :, slot, :].set(k)
        self.v_store = self.v_store.at[gf, :, :, slot, :].set(v)

    def write_tokens(self, pids: Sequence[int], slots: Sequence[int],
                     k_tok: jax.Array, v_tok: jax.Array) -> None:
        """Batched token write: ``k_tok``/``v_tok`` are ``(T, L, Hkv, D)``
        landing at ``(pids[i], slots[i])`` — one scatter per store."""
        if not len(pids):
            return
        self.flush_migrations()
        gf = jnp.asarray(self.global_frames(pids))
        sl = jnp.asarray(np.asarray(slots, np.int32))
        self.k_store = self.k_store.at[gf, :, :, sl, :].set(
            k_tok.astype(self.k_store.dtype))
        self.v_store = self.v_store.at[gf, :, :, sl, :].set(
            v_tok.astype(self.v_store.dtype))

    def gather_pages(self, pids: List[int]) -> jax.Array:
        """Gather page payloads → packed ``(n, L, P, W)``; reads cross
        tiers in place (the global frame space)."""
        n = len(pids)
        L, P = self.cfg.n_layers, self.cfg.page_size
        if not n:
            return jnp.zeros((0, L, P, self.cfg.kv_width), self.k_store.dtype)
        self.flush_migrations()
        gf = jnp.asarray(self.global_frames(pids))
        k = self.k_store[gf]  # (n, L, Hkv, P, D)
        v = self.v_store[gf]
        k = jnp.moveaxis(k, 2, 3).reshape(n, L, P, -1)
        v = jnp.moveaxis(v, 2, 3).reshape(n, L, P, -1)
        return jnp.concatenate([k, v], axis=-1)

    # ---------------------------------------------------------------- #
    # allocation API (used by the engine)
    # ---------------------------------------------------------------- #
    def alloc_page(
        self, page_type: PageType = PageType.ANON,
        tenant: Optional[int] = None,
    ) -> int:
        """Allocate a KV page; ``tenant`` tags the frame for the tiering
        control plane (per-tenant residency/hotness attribution, and
        tenant-aware allocation steering when an arbiter is attached)."""
        page = self.pool.allocate(
            page_type, tenant=-1 if tenant is None else tenant
        )
        # The claimed frame may still source a staged copy (it was freed
        # by a not-yet-flushed demotion): settle before anyone writes it.
        self._flush_if_touches(self._global(page.tier, page.frame))
        return page.pid

    def free_page(self, pid: int) -> None:
        self._cancel_pending(pid)
        self.pool.free(pid)

    def retype(self, pid: int, page_type: PageType) -> None:
        """Reclassify a page (e.g. ANON tail → FILE prefix when sealed)."""
        page = self.pool.pages[pid]
        if page.page_type != page_type:
            node = self.pool.lru[page.tier]
            node.discard(pid, page.page_type)
            page.page_type = page_type
            node.insert(pid, page_type, page.active)

    def occupancy(self) -> Dict[str, int]:
        return self.pool.occupancy()

    # ---------------------------------------------------------------- #
    # residency introspection (the traffic front end's latency model)
    # ---------------------------------------------------------------- #
    def tiers_of(self, pids: Sequence[int]) -> np.ndarray:
        """Tier of each live page (``Tier`` values as an int array)."""
        return np.fromiter(
            (int(self.pool.tier_of(int(p))) for p in pids),
            np.int64, count=len(pids),
        )

    def fast_fraction(self, pids: Sequence[int]) -> float:
        """Fraction of the given pages resident in the fast tier.

        The per-lane residency signal: a sequence whose pages mostly sit
        slow decodes slower (the latency-accounting model charges it the
        slow-tier cost) and makes a cheap pressure victim.  Empty page
        lists read as fully fast (no penalty to charge).
        """
        if not len(pids):
            return 1.0
        fast = int((self.tiers_of(pids) == int(Tier.FAST)).sum())
        return fast / len(pids)
