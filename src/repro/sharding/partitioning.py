"""Logical-axis → mesh-axis translation (DP / TP / EP / SP / FSDP) plus
the data-lake tile placement layer (the ``shards`` axis).

Parameters and activations are annotated with *logical* axis names; a
``MeshRules`` object maps them onto whatever physical mesh the launcher built
(single-pod ``(data, model)`` or multi-pod ``(pod, data, model)``).

Tile placement (the hybrid-query engine's sharded execution path):
``tile_mesh`` builds a one-axis ``("shards",)`` mesh over the first S
devices, and ``strided_tile_layout`` assigns the tile-major ``(T, cap, d)``
bucket layout to shards STRIDED (tile t -> shard t mod S) rather than in
contiguous blocks. Leaves are emitted in tree order, so contiguous blocks
would put whole spatial regions on one shard and every query's best tiles
on a single device; the strided assignment gives each shard an even 1/S
sample of every region, which is what makes per-shard beam rounds cover
the global best-bound frontier at ~1/S the per-shard width. The layout
contract: the padded tile axis is permuted so shard s owns positions
[s*t_local, (s+1)*t_local); pad tiles carry -1 row ids and -inf ball
radii (lower bound +inf — never scanned by a beam, never survive the
V.R triangle bound), so padding is invisible to every pruning rule.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[None, str, Tuple[str, ...]]


@dataclass(frozen=True)
class MeshRules:
    """Maps logical axis names to physical mesh axes."""

    # data-parallel axes (batch). ("pod", "data") on a multi-pod mesh.
    dp: Tuple[str, ...] = ("data",)
    # tensor-parallel axis; None = TP disabled (the "model" axis is then
    # used as extra data/FSDP parallelism — right call for <2B models).
    tp: Optional[str] = "model"
    # FSDP axes for parameter sharding; () disables FSDP.
    fsdp: Tuple[str, ...] = ("data",)
    # sequence-parallel axis for long-context (SP); shares the data axis.
    sp: Tuple[str, ...] = ("data",)
    # physical axis sizes, for divisibility-aware spec construction
    sizes: Tuple[Tuple[str, int], ...] = ()

    def axis_size(self, axes: MeshAxes) -> int:
        if axes is None:
            return 1
        if isinstance(axes, str):
            axes = (axes,)
        table = dict(self.sizes)
        n = 1
        for a in axes:
            n *= table.get(a, 1)
        return n

    def spec(self, *logical: Optional[str]) -> P:
        return P(*[self._resolve(ax) for ax in logical])

    def spec_for(self, shape: Tuple[int, ...],
                 logical: Tuple[Optional[str], ...]) -> P:
        """Shape-aware spec: drops mesh axes that don't divide the dim
        (pjit input/output shardings require exact divisibility; small or
        odd dims — kv_heads=2, 25 heads, odd vocab — fall back to
        replication on that dim and FSDP/TP carries the memory elsewhere).
        """
        out = []
        for dim, ax in zip(shape, logical):
            resolved = self._resolve(ax)
            n = self.axis_size(resolved)
            out.append(resolved if (n > 1 and dim % n == 0) or n == 1
                       else None)
        return P(*out)

    def kv_spec(self, shape: Tuple[int, ...],
                logical: Tuple[Optional[str], ...],
                batch_dim: int, seq_dim: int) -> P:
        """KV-cache spec with sequence-parallel fallback over IDLE axes.

        Decode caches dominate decode-cell memory; any mesh axis not
        consumed by the batch dim shards the cache's sequence dim instead
        (kv-head dims rarely divide a 16-way axis). batch=1 long-context
        decode shards seq over data+model; batched decode shards seq over
        the TP axis the (tiny) decode matmuls leave idle."""
        sp = list(self.spec_for(shape, logical))
        used = set()
        for entry in sp:
            if entry is None:
                continue
            for a in (entry if isinstance(entry, tuple) else (entry,)):
                used.add(a)
        free = [a for a, _ in self.sizes
                if a not in used and a != "pod"]
        if sp[seq_dim] is None and free:
            for cand in (tuple(free), (free[0],)):
                n = self.axis_size(cand)
                if n > 1 and shape[seq_dim] % n == 0:
                    sp[seq_dim] = cand if len(cand) > 1 else cand[0]
                    break
        return P(*sp)

    def flat_spec(self, n_rows: int) -> P:
        """Max sharding for a flat (rows, block) tensor: over fsdp x tp when
        divisible, else fsdp, else replicate. Used for quantized opt state."""
        full = tuple(self.fsdp) + (self.tp,)
        if self.axis_size(full) > 1 and n_rows % self.axis_size(full) == 0:
            return P(full, None)
        f = self.fsdp if len(self.fsdp) > 1 else \
            (self.fsdp[0] if self.fsdp else None)
        if f is not None and n_rows % self.axis_size(f) == 0:
            return P(f, None)
        return P(None, None)

    def _resolve(self, ax: Optional[str]) -> MeshAxes:
        if ax is None:
            return None
        table = {
            "batch": self.dp if len(self.dp) > 1 else (self.dp[0] if self.dp else None),
            "fsdp": self.fsdp if len(self.fsdp) > 1 else (self.fsdp[0] if self.fsdp else None),
            "seq_sp": self.sp if len(self.sp) > 1 else (self.sp[0] if self.sp else None),
            "vocab": self.tp,
            "heads": self.tp,
            "kv_heads": self.tp,
            "ff": self.tp,
            "experts": self.tp,
            "model": self.tp,
            "layers": None,
            # parameter d_model axes are FSDP-sharded; activations never use
            # "embed" (they pass None), so this only affects weights.
            "embed": self.fsdp if len(self.fsdp) > 1
            else (self.fsdp[0] if self.fsdp else None),
            "seq": None,
            "state": None,
        }
        if ax not in table:
            raise KeyError(f"unknown logical axis {ax!r}")
        return table[ax]


def rules_for_mesh(mesh: Mesh, fsdp: bool = True,
                   fsdp_over_pods: bool = False,
                   tensor_parallel: bool = True) -> MeshRules:
    axes = mesh.axis_names
    has_pod = "pod" in axes
    if tensor_parallel:
        dp = ("pod", "data") if has_pod else ("data",)
        tp: Optional[str] = "model"
        base_fsdp: Tuple[str, ...] = ("data",)
    else:
        # pure FSDP/DP: the model axis becomes extra data parallelism
        dp = ("pod", "data", "model") if has_pod else ("data", "model")
        tp = None
        base_fsdp = ("data", "model")
    if not fsdp:
        fsdp_axes: Tuple[str, ...] = ()
    elif fsdp_over_pods and has_pod:
        fsdp_axes = ("pod",) + base_fsdp
    else:
        fsdp_axes = base_fsdp
    sizes = tuple(zip(mesh.axis_names, mesh.devices.shape))
    return MeshRules(dp=dp, tp=tp, fsdp=fsdp_axes, sp=("data",), sizes=sizes)


def shard(x, mesh: Mesh, spec: P):
    """with_sharding_constraint helper usable inside jit under a mesh."""
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec)) if mesh is not None else x


# ---------------------------------------------------------------------------
# Tile placement layer (sharded hybrid-query execution)
# ---------------------------------------------------------------------------
def tile_mesh(shards: int) -> Mesh:
    """A one-axis ``("shards",)`` mesh over the first ``shards`` devices.

    Raises with an actionable message when the backend exposes fewer
    devices — on CPU-only hosts simulated devices come from
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (what CI
    sets to exercise the sharded path)."""
    devs = jax.devices()
    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")
    if shards > len(devs):
        raise ValueError(
            f"tile_mesh(shards={shards}) needs {shards} devices but the "
            f"backend exposes {len(devs)}; on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={shards} before "
            f"importing jax")
    return Mesh(np.asarray(devs[:shards]), ("shards",))


def strided_tile_layout(n_tiles: int, shards: int
                        ) -> Tuple[np.ndarray, int, int]:
    """Strided tile -> shard placement for a ``(T, ...)`` tile axis.

    Returns ``(perm, t_local, t_pad)``: the tile axis is padded to
    ``t_pad = shards * t_local`` positions and permuted so that padded
    position ``s * t_local + j`` holds original tile ``perm[s*t_local+j]``
    (entries >= ``n_tiles`` are padding). Placement is strided — shard s
    owns tiles {t : t mod shards == s} — so each shard holds an even
    1/S sample of the (tree-ordered, spatially clustered) tile sequence;
    see the module docstring for why this beats contiguous blocks."""
    t_local = -(-max(1, n_tiles) // shards)
    t_pad = t_local * shards
    # position s*t_local + j  <-  original tile j*shards + s
    pos = np.arange(t_pad)
    s, j = pos // t_local, pos % t_local
    perm = j * shards + s
    return perm, t_local, t_pad


def manual_shard_map(f, mesh: Mesh, in_specs, out_specs, manual_axes):
    """``jax.shard_map`` of ``f``, manual over ``manual_axes`` only (other
    mesh axes stay automatic), with the replication check off: bodies
    that ``psum``/``all_gather`` make their replicated outputs explicit.
    A rank-0 output takes the spec ``P()``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False,
                         axis_names=set(manual_axes))


def shard_put(x, mesh: Mesh, spec: P):
    """Upload a host array already laid out for ``spec`` — each device
    receives only its slice (no full-array broadcast)."""
    return jax.device_put(x, NamedSharding(mesh, spec))
