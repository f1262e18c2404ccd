"""Core abstractions of grace-tpu: Compressor, Memory, Communicator.

This is a TPU-native (JAX/XLA) re-design of the GRACE decomposition of
compressed data-parallel training (reference: grace_dl/dist/__init__.py:4-52).
The reference models the triad as stateful Python classes holding name-keyed
dicts of residuals/momenta and issuing eager NCCL/MPI calls per tensor. Here:

* **Compressors and memories are frozen dataclasses of static hyperparameters
  with pure methods.** All cross-step state (residual buffers, momenta,
  PowerSGD's Q factor) is an explicit per-leaf state pytree threaded through
  the step — so the whole pipeline jits into one XLA program, and compression
  state checkpoints alongside parameters (the reference never checkpoints it;
  see SURVEY.md §5).
* **Communication is expressed with `jax.lax` collectives over a named mesh
  axis** (`psum` / `all_gather`), executed inside `jax.shard_map` / `pjit`.
  XLA's async scheduling over ICI replaces Horovod's background thread and
  handle/synchronize machinery (reference patch_files/horovod/torch/mpi_ops.py).
* **Payload vs ctx contract** (replaces the reference's loose `(tensors, ctx)`
  pair): `payload` is a tuple of arrays that travel on the wire and may differ
  per rank; `ctx` is decode context that MUST be identical on every rank
  (static Python values, or arrays derived from replicated inputs such as the
  shared RNG key). This is what lets the all-gather path `vmap` decompression
  over the gathered world axis.

Wire-format note: XLA requires static shapes, so the reference's variable-size
payloads (threshold/dgc/adaq, `tensors_size_are_same=False`) become
fixed-capacity payloads whose invalid lanes carry zero values — scatter-add
decompression is then value-exact without a length field.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from grace_tpu.telemetry.scopes import (STAGE_COMPENSATE, STAGE_COMPRESS,
                                        STAGE_EXCHANGE, STAGE_MEMORY_UPDATE,
                                        trace_stage)

# A tuple of arrays that travels on the wire (may differ across ranks).
Payload = Tuple[jax.Array, ...]
# Decode context, identical across ranks (static python data or replicated arrays).
Ctx = Any
# Per-leaf cross-step compressor/memory state (arbitrary pytree, often None).
State = Any

DEFAULT_AXIS = "data"

# The payload-algebra vocabulary (Compressor.payload_algebra): HOW a codec's
# wire payloads compose under element-wise addition across ranks. This is
# the capability the communicators' accumulation paths dispatch on and the
# static analyzers verify — promoted from the old summable_payload bool
# (which survives as a derived property) so the THC-style homomorphic
# codecs can say *which* kind of summable they are:
#
# * "exact"        — decompress(sum of payloads) == sum of decompresses,
#                    bit-for-bit up to float associativity (none, fp16,
#                    randomk's shared-index values, powersgd's in-compress
#                    sum). Float payloads; averaging may divide the payload.
# * "shared_scale" — integer level payloads under ONE scale negotiated
#                    across ranks before encoding (a psum-max collective;
#                    Compressor.negotiate). Payloads add exactly in integer
#                    space — zero re-encode loss per hop — but the
#                    accumulator dtype must cover world * max_level
#                    (Compressor.payload_sum_max_world, enforced at runtime
#                    by the communicators and statically by flow pass 6),
#                    and averaging must divide AFTER the final decode.
# * "sketch"       — linear mergeable sketches (count-sketch tables):
#                    sketch(x) + sketch(y) == sketch(x + y) exactly, so
#                    hop sums merge sketches with zero loss and ONE decode
#                    estimation at the very end (better than
#                    decode-each-then-sum, which pays W estimation errors).
# * None           — per-rank payloads do not compose (per-rank norms,
#                    selection masks, quantile bins); the hop-pipelined
#                    schedules need supports_hop_requant or a gather.
PAYLOAD_ALGEBRAS = ("exact", "shared_scale", "sketch")

# Tolerance contract of the Communicator.recv_wire_bytes model, enforced by
# the static auditor's wire-byte reconciliation pass (grace_tpu.analysis):
# the model must agree with the bytes counted from the actually-traced
# collective schedule within rtol (covers per-shard rounding: ceil'd
# bit-packing, per-shard top-k counts, per-chunk scalar norms) plus a small
# atol floor for scalar/bookkeeping collectives. Widening these to make a
# drifted model "pass" defeats the audit — fix the model instead.
WIRE_MODEL_RTOL = 0.10
WIRE_MODEL_ATOL = 256


def needs_negotiation(compressor) -> bool:
    """Whether the communicators must hoist ``compressor.negotiate``
    BEFORE the stage-1 encode: every ``shared_scale`` codec (the scale IS
    the negotiation), plus codecs that declare ``negotiates = True`` for a
    non-scale shared object (cyclic Top-K's leader index set). One
    predicate so core.step, Ring, Hier, and ReduceScatter can never
    disagree about who negotiates."""
    return (getattr(compressor, "payload_algebra", None) == "shared_scale"
            or getattr(compressor, "negotiates", False))


def negotiation_bytes_for(compressor, n_elems: int, world: int) -> int:
    """Per-rank received bytes of one negotiation collective for an
    ``n_elems``-element compress call: the codec's leaf-aware
    ``negotiation_nbytes_for`` when it declares one (cyclic Top-K's index
    broadcast scales with k), else the world-only
    ``negotiation_nbytes`` (homoqsgd's scalar pmax). ONE accessor shared
    by the telemetry wire plan, the tuner's pricing, and the auditor's
    wire model so the three can never price the same collective
    differently."""
    fn = getattr(compressor, "negotiation_nbytes_for", None)
    if fn is not None:
        return int(fn(int(n_elems), world))
    return int(compressor.negotiation_nbytes(world))


class LinkBytes(NamedTuple):
    """Per-rank received bytes split by the link class they arrive over.

    N ordered tiers, slowest-boundary last: ``ici`` is intra-slice
    interconnect traffic (the fast on-chip torus), ``dcn`` cross-slice
    data-center network traffic (~3.6× slower per the public per-chip
    numbers — see ``bench.PROJECTION_MODEL``), ``wan`` cross-region
    traffic (~100× below DCN — the tier where compression decides
    feasibility, not just step time). ``wan`` defaults to 0 so the 2-tier
    constructor ``LinkBytes(ici, dcn)`` remains an exact alias of every
    pre-region call site and keeps committed evidence bit-identical. The
    tiers are priced separately by the bench projections; their sum is the
    scalar :meth:`Communicator.recv_wire_bytes` the telemetry ring records
    and the static auditor reconciles — the split refines the scalar, it
    never disagrees with it (``ici + dcn + wan == recv_wire_bytes`` is
    enforced by the auditor's wire-reconciliation pass and pinned
    bit-exactly in tests/test_communicators.py / tests/test_region.py for
    every communicator).
    """

    ici: int
    dcn: int
    wan: int = 0

    @property
    def total(self) -> int:
        return self.ici + self.dcn + self.wan

    @property
    def tiers(self) -> tuple:
        """The ordered (ici, dcn, wan) triple — fast link first."""
        return (self.ici, self.dcn, self.wan)


@dataclasses.dataclass(frozen=True)
class Topology:
    """Mesh link topology: which ranks share an ICI domain / a region.

    Ranks ``[k·slice_size, (k+1)·slice_size)`` form one ICI-connected slice;
    traffic between slices rides DCN. ``slice_size=None`` (the default)
    means a single slice spans any world — every byte is ICI, which is the
    regime all committed single-slice measurements ran in.

    ``region_size`` (in RANKS, not slices) adds the third ordered tier:
    ranks ``[ρ·region_size, (ρ+1)·region_size)`` share one region (a
    datacenter/cell of slices joined by DCN); traffic between regions
    rides WAN. It requires ``slice_size`` and must be a whole multiple of
    it — regions are made of whole slices the same way slices are made of
    whole ranks. ``region_size=None`` is the 2-tier layout every existing
    call site built, bit-identical in every model.

    This is deliberately the *minimal* descriptor the wire model needs:
    per-rank received bytes only depend on which boundary the collective's
    schedule crosses (see :meth:`Communicator.recv_link_bytes` for the
    critical-path argument). Richer descriptors (torus dims, per-link
    counts) belong in the bandwidth constants of the projection, not here.
    """

    slice_size: Optional[int] = None
    region_size: Optional[int] = None

    def __post_init__(self):
        if self.slice_size is not None and self.slice_size < 1:
            raise ValueError(f"slice_size must be >= 1 or None; "
                             f"got {self.slice_size}")
        if self.region_size is not None:
            if self.slice_size is None:
                raise ValueError(
                    "region_size requires slice_size — a region is a group "
                    "of whole ICI slices, so a 3-tier layout without a "
                    f"slice tier is contradictory (got region_size="
                    f"{self.region_size}, slice_size=None)")
            if (self.region_size < self.slice_size
                    or self.region_size % self.slice_size):
                raise ValueError(
                    f"region_size {self.region_size} must be a whole "
                    f"multiple of slice_size {self.slice_size} — regions "
                    "are made of whole slices (contiguous-block layout)")

    def crosses_dcn(self, world: int) -> bool:
        """True iff a flat collective over ``world`` ranks spans slices."""
        return self.slice_size is not None and world > self.slice_size

    def crosses_wan(self, world: int) -> bool:
        """True iff a flat collective over ``world`` ranks spans regions."""
        return self.region_size is not None and world > self.region_size

    def flat_tier(self, world: int) -> str:
        """The link tier a *flat* full-axis collective's bytes land on —
        ``'wan'``, ``'dcn'`` or ``'ici'``. The critical-path argument of
        :meth:`Communicator.recv_link_bytes`, shared by every place that
        folds a flat collective's bytes into a per-link split (watch
        gather, shared-scale negotiation pmax, adapt signal reduction):
        the slowest boundary the axis spans prices the whole collective.
        """
        if self.crosses_wan(world):
            return "wan"
        if self.crosses_dcn(world):
            return "dcn"
        return "ici"

    def shrink(self, world: int, lost_ranks) -> Tuple["Topology", int]:
        """The surviving ``(topology, new_world)`` after an elastic resize
        removes ``lost_ranks`` from a contiguous world of ``world`` ranks.

        Granularity decides how much structure survives, finest violated
        level wins (ROADMAP item 4, both halves):

        * **whole regions** lost (3-tier layouts): an R→R−1 WAN-level
          resize — survivors keep ``slice_size`` AND ``region_size``;
          when a single region remains the region tier is vacuous and the
          result collapses to the two-tier ``Topology(slice_size)`` (a
          one-region fleet has no WAN leg to price).
        * **whole slices** lost (but not whole regions): the survivors
          keep ``slice_size`` — losing a slice is a K→K−1 DCN-level
          resize that never touches intra-slice structure, so the
          hierarchical schedule (and its mixed wire split) survives. A
          3-tier layout drops its region tier here: regions with unequal
          surviving slice counts violate the contiguous-equal-regions
          contract, the same conservatism as :meth:`detect` refusing
          uneven slices.
        * **partial** slice losses break the contiguous-equal-slices
          contract entirely (the survivors of a half-dead slice share no
          full ICI domain with anyone), so the result collapses to the
          single-slice flat layout — degraded but honest.
        """
        lost = set(int(r) for r in lost_ranks)
        if not lost:
            return self, world
        bad = [r for r in lost if r < 0 or r >= world]
        if bad:
            raise ValueError(f"lost_ranks {sorted(bad)} outside the world "
                             f"[0, {world})")
        new_world = world - len(lost)
        if new_world < 1:
            raise ValueError(f"cannot shrink world {world} by "
                             f"{len(lost)} ranks — no survivors")
        if self.slice_size is None:
            return Topology(), new_world
        s = self.slice_size
        if world % s:
            raise ValueError(f"world {world} is not a multiple of "
                             f"slice_size {s} — this topology never "
                             "described that world")
        whole = all(
            all(k * s + i in lost for i in range(s))
            for k in sorted({r // s for r in lost}))
        if not whole:
            return Topology(), new_world
        if self.region_size is None:
            return Topology(slice_size=s), new_world
        rz = self.region_size
        if world % rz:
            raise ValueError(f"world {world} is not a multiple of "
                             f"region_size {rz} — this topology never "
                             "described that world")
        touched = sorted({r // rz for r in lost})
        whole_regions = all(
            all(rho * rz + i in lost for i in range(rz)) for rho in touched)
        if not whole_regions:
            # slice-granular loss inside a region: slices survive intact
            # but the regions are no longer equal-sized blocks.
            return Topology(slice_size=s), new_world
        if world // rz - len(touched) <= 1:
            # one region remains — the WAN tier is vacuous.
            return Topology(slice_size=s), new_world
        return Topology(slice_size=s, region_size=rz), new_world

    @classmethod
    def detect(cls, devices=None) -> "Topology":
        """Topology of the live devices: group by the TPU runtime's
        ``slice_index`` when exposed (multislice), and by ``region_index``
        when exposed (cross-region fleets), else a single slice.
        CPU/simulated meshes are always one slice.

        Hardened against the layouts a best-effort grouping used to
        mis-size silently (``len(devices) // len(slices)`` truncates) —
        and ``region_index`` gets the identical treatment ``slice_index``
        has, never a weaker one:

        * a device list where only *some* devices expose ``slice_index``
          (or only some expose ``region_index``) is contradictory — half
          the fleet claims the tier exists, half doesn't — and raises
          rather than guessing a width;
        * uneven slices (e.g. 5+3 devices) or uneven regions have no
          single ``slice_size``/``region_size``; the wire model's
          contiguous-block layout cannot describe them, so they raise
          with the per-group counts instead of flooring to
          ``world // n_groups`` and mis-pricing every projection;
        * regions that are not whole multiples of the detected slice
          width (a slice straddling a region boundary) raise naming both
          counts — the 3-tier descriptor requires regions made of whole
          slices.

        ``slice_index=None`` / ``region_index=None`` (some runtimes stub
        the attributes) count as absent. An empty device list is a single
        slice. A region tier without a slice tier raises (the descriptor
        cannot express it); a single detected region is simply no region
        tier.
        """
        import jax

        devices = list(devices) if devices is not None else jax.devices()

        def group_counts(attr):
            counts: dict = {}
            missing = 0
            for d in devices:
                idx = getattr(d, attr, None)
                if idx is None:
                    missing += 1
                else:
                    counts[idx] = counts.get(idx, 0) + 1
            if counts and missing:
                raise ValueError(
                    f"cannot detect topology: {missing} of {len(devices)} "
                    f"devices expose no {attr} while "
                    f"{len(devices) - missing} do — a heterogeneous device "
                    "list (mixed runtimes / stale handles?) has no "
                    "consistent layout. Pass an explicit Topology(...) "
                    "instead.")
            return counts

        def uniform_size(counts, attr, noun):
            sizes = sorted(set(counts.values()))
            if len(sizes) > 1:
                raise ValueError(
                    f"cannot detect topology: {noun}s are uneven — "
                    f"per-{noun} device counts "
                    f"{dict(sorted(counts.items()))} — so no single "
                    f"{noun}_size describes the layout (the wire model "
                    "assumes contiguous equal blocks). Pass an explicit "
                    "Topology(...) for the layout you mean.")
            return sizes[0]

        slice_counts = group_counts("slice_index")
        region_counts = group_counts("region_index")
        slice_size = (uniform_size(slice_counts, "slice_index", "slice")
                      if len(slice_counts) > 1 else None)
        region_size = (uniform_size(region_counts, "region_index", "region")
                       if len(region_counts) > 1 else None)
        if region_size is not None and slice_size is None:
            raise ValueError(
                "cannot detect topology: devices expose region_index "
                f"({len(region_counts)} regions) but no multi-slice "
                "slice_index layout — a region tier without a slice tier "
                "is contradictory (regions are groups of whole ICI "
                "slices). Pass an explicit Topology(...) instead.")
        if (region_size is not None
                and (region_size < slice_size or region_size % slice_size)):
            raise ValueError(
                f"cannot detect topology: per-region device count "
                f"{region_size} is not a whole multiple of the slice "
                f"width {slice_size} — a slice straddles a region "
                "boundary, which the contiguous-block layout cannot "
                "describe. Pass an explicit Topology(...) for the layout "
                "you mean.")
        if slice_size is None:
            return cls()
        return cls(slice_size=slice_size, region_size=region_size)


SINGLE_SLICE = Topology()


def axis_size(axis_name) -> int:
    """Static size of a bound mesh axis (:func:`jax.lax.axis_size`)."""
    return lax.axis_size(axis_name)


@dataclasses.dataclass(frozen=True)
class Compressor:
    """Lossy gradient codec (reference ABC: grace_dl/dist/__init__.py:15-35).

    Class attributes (mirroring the reference's instance flags,
    grace_dl/dist/__init__.py:18-20):

    * ``average`` — divide the aggregate by world size (mean semantics).
      Sign-based methods set False (grace_dl/dist/compressor/signsgd.py:9).
    * ``tensors_size_are_same`` — retained for API parity/documentation. Under
      XLA every payload is statically shaped, so the all-gather communicator
      never needs the reference's size-exchange dance
      (grace_dl/dist/communicator/allgather.py:16-38).
    * ``vote_aggregate`` — True iff ``aggregate`` is exactly the majority
      vote over ±1 decompressed tensors (signsgd/signum). Gates the
      psum-based :class:`~grace_tpu.comm.SignAllreduce` communicator, which
      re-signs the sum and would silently drop any other aggregate's
      scaling (e.g. EF-SignSGD's 1/lr); the generic ``Allreduce`` also
      routes vote compressors through that psum-vote path.
    * ``payload_algebra`` — the declared composition law of the wire
      payload under cross-rank addition (:data:`PAYLOAD_ALGEBRAS`):
      ``"exact"`` (linear float payloads — none, fp16/bf16, randomk,
      powersgd), ``"shared_scale"`` (integer levels under one negotiated
      scale — homomorphic QSGD), ``"sketch"`` (mergeable linear sketches —
      count-sketch), or ``None`` (payloads do not compose). The reference
      only *documents* the summability matrix (IMPLEMENTING.md:43-45) and
      silently corrupts gradients for e.g. topk+Allreduce; here the
      communicators enforce it and dispatch their accumulation path on it.
      Default None: a new codec must opt in, explicitly, in its own class
      body (the ``compressor-capabilities`` AST rule).
    * ``summable_payload`` — derived, read-only: ``payload_algebra is not
      None``. Kept so every existing call site (communicator gates, tuner
      mirrors, escape-hatch validation) reads the same truth it always did;
      the algebra refines it, never contradicts it.
    * ``supports_hop_requant`` — True iff re-running ``compress`` on a
      *partial sum of decompressed tensors* is a sane (bounded-error)
      re-encoding, which is what the hop-pipelined
      :class:`~grace_tpu.comm.RingAllreduce` does at every reduce-scatter
      hop: decompress → accumulate → requantize (topk re-selects over the
      partial, qsgd re-quantizes against the partial's norm, signsgd
      re-signs — a cascaded vote). Codecs whose payload carries structure a
      partial sum destroys (dgc/threshold capacity masks, onebit's mean
      pair, sketch's bins) must leave this False; linear codecs don't need
      it (``summable_payload`` gives them the exact payload-space
      accumulation path instead). Like ``summable_payload``, this is an
      *enforced* compatibility gate, not documentation. Default False.
    """

    average = True
    tensors_size_are_same = True
    vote_aggregate = False
    payload_algebra = None
    supports_hop_requant = False

    @property
    def summable_payload(self) -> bool:
        """Derived from :attr:`payload_algebra` — True iff payloads compose
        under element-wise addition at all. The pre-algebra bool every
        call site already reads; a codec never declares it directly."""
        return self.payload_algebra is not None

    # True iff the codec runs a pre-encode negotiation collective even
    # though its payload algebra is not "shared_scale" (which implies one):
    # e.g. the ScaleCom-style cyclic local-selection Top-K negotiates a
    # shared INDEX SET (a leader's local selection, broadcast) rather than
    # a scale. Gated through needs_negotiation() so every communicator
    # hoists the same way.
    negotiates = False

    # -- pre-encode negotiation (shared scale / shared selection) -----------
    def negotiate(self, x: jax.Array, axis_name: str, rng=None):
        """The pre-encode negotiation collective: return the
        rank-replicated shared value (a pmax'd scale, a leader's
        broadcast index set) that ``compress(..., shared=...)`` encodes
        against, or None when this codec needs none. Must be called where
        ``axis_name`` is bound; the communicators hoist it BEFORE the
        stage-1 encode so error feedback covers the single negotiated
        encode exactly. ``rng`` is the replicated per-(step, leaf) key —
        rank-identical by the transform's rng contract — for negotiations
        that rotate a leader across steps (cyclic Top-K)."""
        return None

    def negotiation_nbytes(self, world: int) -> int:
        """Per-rank received bytes of one :meth:`negotiate` collective at
        world size ``world`` — 0 for codecs without a negotiation. Priced
        into the telemetry row (``negotiation_bytes``, folded like
        ``watch_bytes``) and the tuner's wire model; the traced collective
        itself is counted by the auditor's wire reconciliation (its scalar
        size sits inside ``WIRE_MODEL_ATOL``)."""
        return 0

    def payload_sum_max_world(self) -> Optional[int]:
        """Largest world size whose payload-space sum stays exact in the
        payload dtype, or None for no codec-specific bound (float "exact"
        payloads are covered by the generic fp16 saturation analysis,
        flow.safe_sum_terms). Shared-scale codecs derive this from ONE
        constant — accumulator iinfo.max // max_level — enforced at runtime
        by the communicators' homomorphic paths and statically by the
        numeric-safety pass and the tuner's numeric gate, mirroring
        :func:`grace_tpu.comm.vote_exact_max_world`."""
        return None

    # -- cross-step state ---------------------------------------------------
    def init_state(self, x: jax.Array) -> State:
        """Initial per-leaf state (e.g. Signum momentum, PowerSGD Q)."""
        return None

    # -- metrics ------------------------------------------------------------
    def wire_nbytes(self, shape, dtype) -> int | None:
        """Analytic bytes-on-wire for one tensor, or None to let
        :func:`grace_tpu.utils.payload_nbytes` shape-trace ``compress``.
        Override when compress cannot be traced without a bound mesh axis
        (PowerSGD's in-compress psum)."""
        return None

    # -- codec --------------------------------------------------------------
    def compress(self, x: jax.Array, state: State, rng: jax.Array
                 ) -> tuple[Payload, Ctx, State]:
        """Encode ``x``; return (wire payload, decode ctx, next state)."""
        raise NotImplementedError

    def decompress(self, payload: Payload, ctx: Ctx) -> jax.Array:
        """Decode one rank's payload back to a dense tensor."""
        raise NotImplementedError

    def aggregate(self, stacked: jax.Array) -> jax.Array:
        """Reduce decompressed tensors stacked along a leading world axis.

        Default: sum (reference grace_dl/dist/__init__.py:32-34). SignSGD
        overrides with a majority vote.
        """
        return jnp.sum(stacked, axis=0)

    # -- the kernel-resident wire path (ROADMAP item 2) ---------------------
    # The communicators' hop/boundary arithmetic is routed through these
    # three hooks so a codec can swap in fused Pallas kernels
    # (grace_tpu.ops.pallas_wire) without the schedules knowing. The
    # defaults reproduce the staged spellings the schedules ran before the
    # hooks existed — BIT-EXACTLY, which is what lets an override claim
    # "bit-identical to the staged path" against a stable reference.

    def decode_accumulate(self, payloads: Sequence[Payload],
                          ctxs: Sequence[Ctx]) -> jax.Array:
        """Decode K payloads and sum them into one dense partial, in
        sequence order — the ring hop's ``decompress(recv) +
        decompress(own)`` and the requant boundary's decode-side sum.
        Codecs with fused decode→accumulate kernels override this; the
        default is the staged left-to-right spelling."""
        out = self.decompress(payloads[0], ctxs[0])
        for payload, ctx in zip(payloads[1:], ctxs[1:]):
            out = out + self.decompress(payload, ctx)
        return out

    def payload_add(self, a: Payload, b: Payload) -> Payload:
        """Payload-space ``a + b`` for summable payloads (the exact-path
        ring hop). Default: element-wise tuple add — only meaningful when
        :attr:`summable_payload`; packed shared-scale codecs override
        with unpack→add→repack (optionally fused)."""
        return tuple(r + o for r, o in zip(a, b))

    def payload_sum(self, stacked: Payload) -> Payload:
        """Payload-space sum over a stacked leading world axis (the
        gather-boundary accumulate of the homomorphic paths). Default:
        dtype-pinned ``jnp.sum`` per leaf — the accumulator IS the
        payload dtype, so overflow is governed by
        :meth:`payload_sum_max_world`, never silently widened away."""
        return tuple(jnp.sum(t, axis=0, dtype=t.dtype) for t in stacked)

    def wire_fused(self) -> bool:
        """True when this codec's fused wire-path kernels would actually
        run under the current selection rule (``use_pallas`` knob, backend
        and the GRACE_DISABLE_PALLAS[_WIRE] escape hatches — ONE rule,
        :func:`grace_tpu.ops.pallas_mode`). The communicators consult this
        before swapping a gather boundary's staged vmap-decompress +
        aggregate spelling for the fused K-way ``decode_accumulate`` pass:
        the two associate float adds differently, so the swap must never
        happen behind a disabled kernel's back — staged runs must stay
        bit-identical to the committed schedules. Default False (no wire
        kernels)."""
        return False


@dataclasses.dataclass(frozen=True)
class Memory:
    """Error-feedback memory (reference ABC: grace_dl/dist/__init__.py:4-13).

    The reference mutates name-keyed dicts; here ``compensate``/``update``
    thread an explicit per-leaf state pytree. ``compensate`` may also update
    state (DGC's momentum/accumulation buffers mutate during compensate —
    grace_dl/dist/memory/dgc.py:16-30 — hence the two-stage contract).
    """

    def init_state(self, x: jax.Array) -> State:
        return None

    def compensate(self, x: jax.Array, state: State
                   ) -> tuple[jax.Array, State]:
        """Fold residual state into the incoming gradient."""
        return x, state

    def update(self, compensated: jax.Array, payload: Payload, ctx: Ctx,
               compressor: Compressor, state: State) -> State:
        """Store the new residual = compensated - decompress(payload)."""
        return state


@dataclasses.dataclass(frozen=True)
class Communicator:
    """Collective exchange of compressed payloads over a named mesh axis.

    Reference ABC: grace_dl/dist/__init__.py:37-52. ``exchange`` must be
    called inside a `shard_map`/`pjit` context where ``axis_name`` is bound.
    The reference's async handle machinery (grace_dl/torch/__init__.py:37-58)
    has no analog: XLA schedules and overlaps collectives itself.
    """

    axis_name: str = DEFAULT_AXIS

    # True for communicators that re-chunk the gradient into per-rank shards
    # inside ``step`` (TwoShotAllreduce, RingAllreduce). Shard-parallel
    # steps carry their own collective schedule (all_to_all / ppermute) and
    # are not a validated target for ``fusion='grouped'`` vmapping — the
    # transform gates on this flag at build time.
    shard_parallel = False

    def world_size(self) -> jax.Array:
        return lax.psum(1, self.axis_name)

    def shard_spec(self, n: int) -> tuple[int, int, int]:
        """Equal-shard split of an ``n``-element flat buffer over the bound
        mesh axis: ``(world, shard_elems, pad)`` with
        ``world * shard_elems == n + pad``. The chunk schedule shared by the
        shard-parallel communicators (``TwoShotAllreduce``,
        ``RingAllreduce``); must be called where ``axis_name`` is bound, and
        is static at trace time (XLA shapes stay static)."""
        w = axis_size(self.axis_name)
        pad = (-n) % w
        return w, (n + pad) // w, pad

    def _recv_total_bytes(self, payload_nbytes: int, n_elems: int,
                          world: int, vote: bool = False) -> int:
        """Schedule-total received bytes per rank — the per-communicator
        formula. Subclasses override THIS (not ``recv_wire_bytes`` /
        ``recv_link_bytes``), so the scalar model and the per-link split
        share one implementation and can never drift apart. Default:
        gather-style, every other rank's payload arrives
        (``Allgather``/``Broadcast``); reduce-style subclasses override.
        """
        return payload_nbytes * max(0, world - 1)

    def recv_link_bytes(self, payload_nbytes: int, n_elems: int, world: int,
                        topology: Optional[Topology] = None,
                        vote: bool = False) -> LinkBytes:
        """Per-rank received bytes split by link class — ``(ici, dcn, wan)``.

        The split is the **critical-path rank's** view of the flat schedule
        the collectives ride: in a ring/gather laid over the mesh axis, each
        rank receives every byte over its single incoming neighbor link, and
        the collective finishes when the slowest rank does. When
        ``topology`` says the axis spans more than one ICI slice, some
        rank's incoming link is a DCN boundary link — every pipelined chunk
        crosses it, so that rank (and therefore the collective) is priced
        entirely at DCN; when the axis additionally spans regions, some
        rank's incoming link is a WAN boundary link and the whole bill
        lands one tier lower still. Hence a *flat* communicator's breakdown
        is all-ICI within one slice, all-DCN beyond it, and all-WAN the
        moment the axis crosses regions (:meth:`Topology.flat_tier`): the
        honest statement of why flat schedules collapse at multislice scale
        (topk+allgather losing to dense at W=256 on DCN) collapses harder
        at fleet scale. The hierarchical communicator
        (:class:`grace_tpu.comm.HierarchicalAllreduce`) earns a genuinely
        mixed split by overriding this method — bench projections,
        telemetry's ``wire_bytes_ici``/``_dcn``/``_wan`` fields, and the
        auditor all pick it up for free.

        ``topology=None`` means :data:`SINGLE_SLICE` (all ICI), matching
        every committed single-slice measurement.
        """
        total = int(self._recv_total_bytes(payload_nbytes, n_elems, world,
                                           vote=vote))
        topo = topology if topology is not None else SINGLE_SLICE
        tier = topo.flat_tier(world)
        if tier == "wan":
            return LinkBytes(ici=0, dcn=0, wan=total)
        if tier == "dcn":
            return LinkBytes(ici=0, dcn=total)
        return LinkBytes(ici=total, dcn=0)

    def recv_wire_bytes(self, payload_nbytes: int, n_elems: int, world: int,
                        vote: bool = False) -> int:
        """Logical bytes RECEIVED per rank per step at world size ``world``.

        ``payload_nbytes`` is one rank's whole-gradient payload
        (:func:`grace_tpu.utils.metrics.payload_nbytes`), ``n_elems`` the
        dense element count (vote collectives move dense bf16 votes, not the
        packed payload), ``vote`` whether the exchange takes a majority-vote
        route. This is the communicator-aware wire model shared by the bench
        projections (``bench.recv_bytes_model``) and the in-graph telemetry
        ring's ``wire_bytes`` field — payload bytes alone are communicator-
        blind and cannot rank e.g. ring/two-shot's O(k) against allgather's
        O(W·k). Defined as the sum of the per-link split
        (:meth:`recv_link_bytes`), so the scalar and the breakdown are
        structurally one model.

        This model is *audited*: the static analyzer
        (:mod:`grace_tpu.analysis`, ``tools/graft_lint.py``) counts the
        received bytes of the actually-traced collective schedule and
        fails CI when the model drifts beyond ``WIRE_MODEL_RTOL`` /
        ``WIRE_MODEL_ATOL`` — an override that stops matching its
        ``exchange``/``step`` is a lint error, not a silent telemetry lie.
        """
        return self.recv_link_bytes(payload_nbytes, n_elems, world,
                                    vote=vote).total

    def wire_overlap_fraction(self) -> float:
        """Fraction of this communicator's wire time the schedule itself
        can hide behind hop compute — the ``wire_pipeline`` discount the
        tuner's cost model and the bench projections apply. 0.0 for every
        serial schedule (the NO-OVERLAP upper bound stands unchanged);
        the pipelined ring/hier schedules override with their
        double-buffer bound, and flow pass 5's chain count is the static
        referee that the traced graph actually exposes the claimed
        independent chains."""
        return 0.0

    def exchange(self, payload: Payload, ctx: Ctx, compressor: Compressor
                 ) -> jax.Array:
        """Exchange payloads across ranks; return the aggregated dense tensor."""
        raise NotImplementedError

    # -- the universal 6-stage pipeline ------------------------------------
    def step(self, x: jax.Array, mem_state: State, comp_state: State,
             memory: Memory, compressor: Compressor, rng: jax.Array
             ) -> tuple[jax.Array, State, State]:
        """compensate → compress → update-residual → exchange.

        Mirrors grace_dl/dist/__init__.py:47-52 but returns next states
        functionally instead of mutating dicts.

        Fused fast path: when the memory declares linear error feedback
        (``linear_feedback_coeffs``: compensate = β·state + γ·x, update =
        compensated − decompress) and the compressor offers
        ``fused_feedback_compress`` (e.g. chunk-mode Top-K's one-HBM-pass
        Pallas kernel, ops/pallas_topk.py), the three local stages collapse
        into one call with bit-identical semantics.
        """
        coeffs = getattr(memory, "linear_feedback_coeffs", None)
        fused = getattr(compressor, "fused_feedback_compress", None)
        if coeffs is not None and fused is not None and mem_state is not None:
            with trace_stage(STAGE_COMPRESS):
                fused_out = fused(x, mem_state, coeffs, rng,
                                  world=lambda: axis_size(self.axis_name))
            if fused_out is not None:
                payload, ctx, mem_state = fused_out
                with trace_stage(STAGE_EXCHANGE):
                    out = self.exchange(payload, ctx, compressor)
                return out, mem_state, comp_state
        # Named scopes make each stage attributable in a Perfetto/XProf
        # device trace (see grace_tpu.telemetry.scopes) — otherwise the
        # whole pipeline renders as anonymous XLA fusions.
        with trace_stage(STAGE_COMPENSATE):
            compensated, mem_state = memory.compensate(x, mem_state)
        # Pre-encode negotiation, hoisted BEFORE the encode: the codec's
        # collective (shared-scale pmax, cyclic Top-K's leader index
        # broadcast) makes the shared object — and thus the decode ctx —
        # rank-identical, so payloads sum homomorphically AND error
        # feedback covers the single negotiated encode exactly. Skipped
        # when the mesh axis is unbound (single-process Identity use):
        # the codec's local fallback decodes its own payload exactly
        # there.
        shared = None
        if needs_negotiation(compressor):
            try:
                with trace_stage(f"{STAGE_EXCHANGE}/negotiate_scale"):
                    shared = compressor.negotiate(compensated,
                                                  self.axis_name, rng=rng)
            except NameError:           # unbound axis: no mesh, no peers
                shared = None
        with trace_stage(STAGE_COMPRESS):
            if shared is None:
                payload, ctx, comp_state = compressor.compress(
                    compensated, comp_state, rng)
            else:
                payload, ctx, comp_state = compressor.compress(
                    compensated, comp_state, rng, shared=shared)
        with trace_stage(STAGE_MEMORY_UPDATE):
            mem_state = memory.update(compensated, payload, ctx, compressor,
                                      mem_state)
        with trace_stage(STAGE_EXCHANGE):
            out = self.exchange(payload, ctx, compressor)
        return out, mem_state, comp_state
