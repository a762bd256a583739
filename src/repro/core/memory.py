"""Memory accounting, budgets, and the per-stage footprint model (paper §2.1).

dMath pools unused GPU memory to avoid CUDA alloc/IB-registration costs and
keeps operands persistent on device.  Under XLA the arena allocator plays the
pool's role and buffer *donation* gives in-place update steps; what remains
for the framework is (a) making donation systematic and (b) a footprint model
that predicts per-device bytes for a (config, plan, schedule) cell before
anything is allocated.  The model here is *pipeline-aware*: it prices each
stage of a GPipe/1F1B cell separately (weights at 1/S of the layers,
activations times the schedule's in-flight microbatch count, the
stage-boundary stash, and the edge-stage embed/head logits), and it is what
``core/planner.py`` uses to refuse OOM (dp, tp, pp, M) candidates and what
``launch/dryrun.py`` prints as the footprint table.

Budget discipline: a single :class:`MemoryBudget` object carries both the
raw HBM bytes and the usable-fraction headroom, so every consumer (planner,
dry-run, train fail-fast) compares against the same ``budget.usable`` —
there is exactly one headroom constant in the repo and it lives here.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp

from . import chips
from .chips import GIB
from .layout import Layout

#: The single headroom constant: fraction of physical HBM the footprint
#: model may plan into.  The remainder covers the XLA arena slop, compiler
#: scratch, and infeed buffers the model does not see.
DEFAULT_HEADROOM = 0.9


@dataclasses.dataclass(frozen=True)
class MemoryBudget:
    """Per-device HBM budget — the single source of truth for headroom.

    Every fits/OOM decision in the repo (planner candidate refusal, the
    dry-run verdict column, ``launch/train.py`` fail-fast) goes through
    ``budget.usable`` so no caller can apply its own constant.
    """

    hbm_bytes: int
    headroom: float = DEFAULT_HEADROOM
    platform: str = "custom"

    @property
    def usable(self) -> int:
        return int(self.hbm_bytes * self.headroom)

    @property
    def gib(self) -> float:
        return self.hbm_bytes / GIB

    def describe(self) -> str:
        return (f"{self.platform} {self.gib:.1f} GiB "
                f"(usable {self.usable / GIB:.1f} GiB "
                f"@ headroom {self.headroom:.2f})")


#: Per-chip budgets, one per entry of :data:`repro.core.chips.CHIPS`
#: (``cpu`` is the fake-device test stand-in, kept at v5e parity).
HBM_BUDGETS: Dict[str, MemoryBudget] = {
    name: MemoryBudget(c.hbm_bytes, platform=name)
    for name, c in chips.CHIPS.items()}

#: budget of analytic planning that names no device (no mesh, no platform)
DEFAULT_PLATFORM = "v5e"


def budget_for(mesh=None, *, hbm_gib: Optional[float] = None,
               platform: Optional[str] = None,
               headroom: Optional[float] = None) -> MemoryBudget:
    """Resolve the per-device budget for a mesh.

    Priority: explicit ``hbm_gib`` override (the ``--hbm-gib`` flag) >
    explicit ``platform`` key > the mesh's device kind > the v5e default
    when neither a platform nor a mesh is given.  An unknown platform or
    device kind raises: no device silently gets another one's budget.
    """
    if hbm_gib is not None:
        return MemoryBudget(int(hbm_gib * GIB),
                            headroom=(headroom if headroom is not None
                                      else DEFAULT_HEADROOM),
                            platform=platform or "override")
    key = platform
    if key is None and mesh is not None:
        key = chips.chip_key(mesh.devices.flat[0].device_kind)
    key = key or DEFAULT_PLATFORM
    if key not in HBM_BUDGETS:
        raise ValueError(f"no HBM budget for platform {key!r}; known: "
                         f"{sorted(HBM_BUDGETS)}")
    base = HBM_BUDGETS[key]
    if headroom is not None and headroom != base.headroom:
        return dataclasses.replace(base, headroom=headroom)
    return base


def nbytes(shape, dtype) -> int:
    return math.prod(shape) * jnp.dtype(dtype).itemsize


@dataclasses.dataclass
class Footprint:
    """Per-device byte budget, by category."""

    params: int = 0
    optimizer: int = 0
    gradients: int = 0
    activations: int = 0
    stash: int = 0          # stage-boundary microbatch stash (pipeline)
    logits: int = 0         # edge-stage embed/head fp32 logits + cotangent
    kv_cache: int = 0
    workspace: int = 0

    _FIELDS = ("params", "optimizer", "gradients", "activations",
               "stash", "logits", "kv_cache", "workspace")

    @property
    def total(self) -> int:
        return sum(getattr(self, f) for f in self._FIELDS)

    @property
    def calibrated_total(self) -> float:
        """``total`` scaled by the active calibration table's
        measured/predicted peak ratio (:mod:`repro.core.calibrate`;
        1.0 without a table) — the model's systematic bias divided out."""
        from repro.core import calibrate
        return self.total * calibrate.memory_scale()

    def fits(self, budget: Union[MemoryBudget, int, None] = None) -> bool:
        """Does this footprint fit ``budget.usable``?

        The headroom lives on the budget object (single source of truth);
        a raw byte count is wrapped with the default headroom.  The
        comparison uses :attr:`calibrated_total`, so an installed
        calibration table corrects the model's measured bias before the
        planner refuses a candidate.
        """
        budget = as_budget(budget)
        return self.calibrated_total <= budget.usable

    def report(self) -> str:
        rows = [(k, getattr(self, k)) for k in self._FIELDS]
        rows.append(("TOTAL", self.total))
        return "\n".join(f"  {k:<12} {v / GIB:8.3f} GiB" for k, v in rows)


def as_budget(budget: Union[MemoryBudget, int, None]) -> MemoryBudget:
    if budget is None:
        return HBM_BUDGETS[DEFAULT_PLATFORM]
    if isinstance(budget, MemoryBudget):
        return budget
    return MemoryBudget(int(budget))


# --------------------------------------------------------------------------
# per-stage footprint model
# --------------------------------------------------------------------------

#: The fp32 logits block is live twice around the loss: once as the forward
#: value feeding logsumexp, once as its same-shaped cotangent in backward.
LOGITS_LIVE_FACTOR = 2

#: Coarse transient working set of one layer body (attention scores chunk,
#: MLP/SSD intermediates), in residual-block units.  Flash-style chunking
#: keeps this O(blocks), not O(seq^2).
WORKSPACE_BLOCKS = 4


def _edge_param_count(cfg) -> int:
    """Embed + unembed + final norm parameters (padded vocab — what is
    actually allocated)."""
    V = getattr(cfg, "padded_vocab", None) or getattr(cfg, "vocab_size", 0)
    D = getattr(cfg, "d_model", 0)
    return 2 * V * D + D


def _layer_param_count(cfg) -> int:
    total = cfg.param_count() if hasattr(cfg, "param_count") else 0
    return max(0, total - _edge_param_count(cfg))


def stage_footprint(cfg, *, local_batch: int, seq_len: int,
                    stage: int = 0, n_stages: int = 1,
                    num_microbatches: int = 1,
                    schedule: Optional[str] = None,
                    zero_shards: int = 1, tp_shards: int = 1,
                    fsdp_shards: int = 1,
                    param_itemsize: int = 2, moment_itemsize: int = 4,
                    edge_gated: bool = True,
                    stash_slots: Optional[int] = None) -> Footprint:
    """Predicted per-device bytes for ONE pipeline stage of a train cell.

    The model follows the executable paths in ``train/step.py`` and
    ``pipeline/schedule.py``:

    - **params**: this stage's 1/S slice of the layer stack plus the edge
      params (embed/unembed/final norm), which the SPMD pipeline keeps
      resident on every stage; both divided by the TP/FSDP shard counts.
    - **optimizer**: fp32 master + two moments of the stage's params,
      ZeRO-sharded over the data axis (``zero_shards``).
    - **gradients**: the fp32 accumulator.  The pipeline shard_map holds it
      at full stage size per device; the non-pipelined path reduce-scatters
      onto the ZeRO shards.
    - **activations**: per-layer residual blocks times the schedule's
      in-flight microbatch count — M for GPipe (the scan transpose replays
      all M), one for 1F1B (stage-input stash + recompute) and for the
      non-pipelined microbatch scan.
    - **stash**: the stage-boundary microbatch inputs a schedule keeps
      live: M + S - 1 scan carries for GPipe, the min(M, 2S-1) ring for
      the eager 1F1B (see ``pipeline/costs.py:min_stash_slots``).
    - **logits**: the fp32 (B_mb, S, V) block plus its backward cotangent.
      Schedule-dependent in a way that matters more than any other term:

      * non-pipelined / 1F1B — transient per microbatch (the microbatch
        scan and the per-tick vjp both consume it before the next one),
        so ``LOGITS_LIVE_FACTOR`` blocks; with edge gating only the last
        stage pays (the ``lax.cond`` branch never allocates on interior
        stages), ungated every stage pays.
      * GPipe — the tick scan's autodiff stashes the head residuals
        (logits + the masked fp32 copy the loss keeps) for EVERY tick,
        and the stacked residual buffer allocates on every device of the
        SPMD program, so all stages pay (M + S - 1) *
        ``LOGITS_LIVE_FACTOR`` blocks regardless of gating.  This is why
        GPipe edge peaks dominate the measured ``--pp`` dry-runs and why
        the planner steers large-vocab pipeline cells to 1F1B.
    - **workspace**: a coarse transient term for the layer body.
    """
    S = max(1, n_stages)
    M = max(1, num_microbatches)
    L = max(1, getattr(cfg, "n_layers", 1) or 1)
    D = getattr(cfg, "d_model", 0) or 0
    V = getattr(cfg, "padded_vocab", None) or getattr(cfg, "vocab_size", 0)
    pipelined = schedule in ("gpipe", "1f1b") and S > 1

    layers_stage = L / S
    layer_count = _layer_param_count(cfg) * layers_stage / L
    edge_count = _edge_param_count(cfg)
    stage_count = (layer_count + edge_count) / tp_shards

    params = int(param_itemsize * stage_count / fsdp_shards)
    optimizer = int((4 + 2 * moment_itemsize) * stage_count / zero_shards)
    grad_shards = 1 if pipelined else zero_shards
    gradients = int(4 * stage_count / grad_shards)

    b_mb = max(1, local_batch // M)
    act_block = b_mb * seq_len * D * 2          # one bf16 residual block
    if pipelined:
        from repro.pipeline import costs as pipe_costs
        in_flight = pipe_costs.in_flight_microbatches(schedule, S, M)
        if schedule == "gpipe":
            activations = int(in_flight * layers_stage * act_block)
            stash = (M + S - 1) * act_block
        else:                                    # 1f1b: recompute one mb
            activations = int(layers_stage * act_block)
            slots = stash_slots or pipe_costs.min_stash_slots(S, M)
            stash = slots * act_block
    else:
        activations = int(layers_stage * act_block)
        stash = 0

    logits_block = b_mb * seq_len * max(1, V // max(1, tp_shards)) * 4
    if pipelined and schedule == "gpipe":
        # the tick scan stashes head residuals for every tick, on every
        # device (stacked scan residuals are program-uniform under SPMD)
        logits = (M + S - 1) * LOGITS_LIVE_FACTOR * logits_block
    elif (not pipelined) or (not edge_gated) or stage == S - 1:
        logits = LOGITS_LIVE_FACTOR * logits_block
    else:
        logits = 0

    f_eff = max(D,
                getattr(cfg, "d_ff", 0) or 0,
                getattr(cfg, "d_inner", 0) or 0)
    workspace = WORKSPACE_BLOCKS * b_mb * seq_len * max(D, f_eff
                                                        // max(1, tp_shards)) * 2

    return Footprint(params=params, optimizer=optimizer,
                     gradients=gradients, activations=activations,
                     stash=int(stash), logits=int(logits),
                     workspace=int(workspace))


def estimate_stage_footprints(cfg, *, local_batch: int, seq_len: int,
                              n_stages: int = 1, num_microbatches: int = 1,
                              schedule: Optional[str] = None,
                              **kw) -> List[Footprint]:
    """One :class:`Footprint` per pipeline stage (a single entry when the
    cell is not pipelined)."""
    S = max(1, n_stages)
    sched = schedule if S > 1 else None
    return [stage_footprint(cfg, local_batch=local_batch, seq_len=seq_len,
                            stage=s, n_stages=S,
                            num_microbatches=num_microbatches,
                            schedule=sched, **kw)
            for s in range(S)]


def footprints_for_mesh(cfg, mesh, *, global_batch: int, seq_len: int,
                        num_microbatches: int = 1,
                        schedule: str = "gpipe",
                        moment_itemsize: int = 4) -> List[Footprint]:
    """Per-stage footprints for a train cell on a concrete mesh.

    The single mesh-to-model derivation shared by ``launch/dryrun.py``'s
    table and ``launch/train.py``'s fail-fast (so the two launch surfaces
    cannot drift): DP shard count from the batch axes, pipeline stages
    from the ``pipe`` axis, TP shards from ``model``; ``schedule`` only
    applies when the mesh actually has pipeline stages.
    """
    nb = math.prod(mesh.shape.get(a, 1) for a in ("pod", "data")) or 1
    pp = mesh.shape.get("pipe", 1)
    return estimate_stage_footprints(
        cfg, local_batch=max(1, global_batch // nb), seq_len=seq_len,
        n_stages=pp, num_microbatches=max(1, num_microbatches),
        schedule=schedule if pp > 1 else None,
        zero_shards=nb, tp_shards=mesh.shape.get("model", 1),
        moment_itemsize=moment_itemsize)


def peak_stage_footprint(footprints: Sequence[Footprint]) -> Footprint:
    """The stage with the largest total — the per-device peak of an SPMD
    pipeline (every device compiles the same program; the heaviest stage
    sets the arena)."""
    return max(footprints, key=lambda f: f.total)


def compiled_peak_bytes(compiled) -> int:
    """Measured per-device peak of a compiled executable — the measured
    side of every predicted-vs-measured comparison (dry-run, the
    memory_model benchmark, and the acceptance tests all use THIS
    definition, so the quantities cannot drift apart)."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def footprint_table(footprints: Sequence[Footprint],
                    budget: Union[MemoryBudget, int, None] = None) -> str:
    """Human-readable per-stage table with a fits/OOM verdict column."""
    budget = as_budget(budget)
    cols = Footprint._FIELDS
    head = ("stage " + "".join(f"{c[:6]:>9}" for c in cols)
            + f"{'total':>9}  verdict")
    lines = [head]
    for s, f in enumerate(footprints):
        cells = "".join(f"{getattr(f, c) / GIB:9.3f}" for c in cols)
        verdict = "fits" if f.fits(budget) else "OOM"
        lines.append(f"{s:>5} {cells}{f.total / GIB:9.3f}  {verdict}")
    ok = all(f.fits(budget) for f in footprints)
    lines.append(f"budget {budget.describe()} -> "
                 + ("FITS" if ok else "OOM"))
    return "\n".join(lines)


class Ledger:
    """Running account of device-resident tensors by (name -> bytes/device).

    The dry-run fills one from abstract values; training fills one from real
    arrays.  It is the bookkeeping side of "persistent storage of operands".
    """

    def __init__(self, mesh: Optional[jax.sharding.Mesh] = None):
        self.mesh = mesh
        self.entries: Dict[str, int] = {}

    def add(self, name: str, shape, dtype, layout: Optional[Layout] = None) -> int:
        if layout is not None and self.mesh is not None:
            b = layout.bytes_per_device(shape, dtype, self.mesh)
        else:
            b = nbytes(shape, dtype)
        self.entries[name] = self.entries.get(name, 0) + b
        return b

    def add_tree(self, name: str, tree, layouts=None) -> int:
        leaves = jax.tree.leaves(tree)
        lls = jax.tree.leaves(layouts) if layouts is not None else [None] * len(leaves)
        total = 0
        for i, (leaf, ll) in enumerate(zip(leaves, lls)):
            total += self.add(f"{name}/{i}", leaf.shape, leaf.dtype, ll)
        return total

    @property
    def total(self) -> int:
        return sum(self.entries.values())


def donate_state(fn, state_argnum: int = 0):
    """Donate the state argument so updates are in-place (the pool analogue)."""
    return jax.jit(fn, donate_argnums=(state_argnum,))


def tree_bytes(tree: Any) -> int:
    return sum(nbytes(x.shape, x.dtype) for x in jax.tree.leaves(tree)
               if hasattr(x, "shape"))
