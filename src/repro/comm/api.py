"""`CommSpec` — one declarative description of the communication stack.

Everything the trainer needs to know about gradient exchange lives in one
frozen dataclass: *what* to send (``strategy`` + ``compressor`` +
``bucket_size``), *how* to move it (``backend``, resolved per mesh through
:mod:`repro.comm.backends`), and the two optional riders (``overlap``
pipelining, ``byz`` fault injection / tolerance). :func:`make_aggregator` is
the single construction path — it validates the spec once
(:meth:`CommSpec.validate`, the consolidated error taxonomy of
:mod:`repro.comm.errors`), resolves the backend, and dispatches to the
bucketed / overlapped implementation. The old per-path factories
(``make_bucketed_aggregator`` / ``make_overlapped_aggregator``) remain as
thin deprecated shims over this function.

Validation ordering is part of the contract (tests pin the messages):
structural checks (unknown strategy/backend, compressor wire-format,
overlap/byz path guards) always run; the world-dependent tolerance check
(``2·byz_f < W``) runs only once ``world`` is known — so a spec can be
validated early at config time and again, fully, at build time.
"""

from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING

import jax

from repro.comm import bucketize, compressed, robust
from repro.comm.errors import PathConfigError, UnknownStrategyError, WireFormatError
from repro.configs.base import ByzConfig, OverlapConfig
from repro.core.compressors import Compressor, ScaledSignCompressor, get_compressor
from repro.kernels import ops

if TYPE_CHECKING:  # repro.fed imports comm.errors — keep the runtime edge one-way
    from repro.fed.spec import FedSpec

AxisNames = tuple[str, ...]


@dataclasses.dataclass(frozen=True)
class CommSpec:
    """Declarative spec of the gradient-communication stack.

    ``compressor`` accepts a registry name (``"scaled_sign"``), a
    :class:`Compressor` instance, or ``None`` (strategy default: scaled sign
    for the EF strategies). ``backend`` names a transport from
    ``repro.comm.backends.BACKENDS`` or ``"auto"`` (deterministic per mesh:
    ``ef_ring`` → ``ring``; ``ef_allgather`` on a TPU ring consults the
    DMA-hop latency oracle for ``pallas_dma``; everything else — including
    the robust strategies, whose slot-native decode runs on every backend —
    → ``xla``).
    ``bucket_size=None`` selects the per-leaf fallback path in
    ``repro.core.aggregation`` (train-step only; the bucketed aggregator
    itself always has a layout). ``telemetry`` turns on the in-graph
    :class:`repro.obs.telemetry.Telemetry` aux output (``"off"`` | ``"full"``;
    off compiles to the exact pre-telemetry program).
    """

    strategy: str = "dense"
    compressor: Compressor | str | None = None
    bucket_size: int | None = bucketize.DEFAULT_BUCKET_SIZE
    backend: str = "auto"
    byz: ByzConfig | None = None
    overlap: OverlapConfig | None = None
    telemetry: str = "off"
    # federated rider (repro.fed): simulate a client population over the
    # same bucket wire format — per-round cohorts, FedAvg weights, per-client
    # EF residual pools; None = the data-parallel exchange
    fed: "FedSpec | None" = None

    @property
    def resolved_compressor(self) -> Compressor | None:
        """The compressor instance (registry names resolved), or ``None`` to
        let each path apply its strategy default."""
        if isinstance(self.compressor, str):
            return get_compressor(self.compressor)
        return self.compressor

    @property
    def byz_f(self) -> int:
        """Declared adversary tolerance (0 when no byz rider)."""
        return self.byz.f if self.byz is not None else 0

    def world_of(self, mesh, ef_axes: AxisNames) -> int:
        from repro.comm import collective

        return collective.world_size(mesh, ef_axes)

    def validate(self, *, world: int | None = None, ef_axes: AxisNames | None = None) -> "CommSpec":
        """Raise a :class:`repro.comm.errors.CommSpecError` subclass (all
        ``ValueError``) on any invalid combination; return ``self`` otherwise.

        The one validation site for what used to live in three places
        (``train/steps.py`` path guards, ``collective.py`` strategy checks,
        ``robust.validate_tolerance`` call ordering). ``world``/``ef_axes``
        unlock the mesh-dependent checks; without them only structural
        validation runs.
        """
        from repro.comm import backends, collective

        if self.strategy not in collective.STRATEGIES:
            raise UnknownStrategyError(
                f"unknown bucketed strategy {self.strategy!r}; options: {collective.STRATEGIES}"
            )
        if self.backend not in backends.BACKEND_CHOICES:
            backends.lookup(self.backend)  # raises UnknownBackendError w/ options
        comp = self.resolved_compressor or ScaledSignCompressor()
        if self.strategy == "ef_alltoall" and not compressed.is_sign(comp):
            raise WireFormatError("ef_alltoall supports sign compressors (wire format)")
        if self.overlap is not None and (self.strategy == "dense" or self.bucket_size is None):
            raise PathConfigError(
                "overlap_groups needs the bucketed EF path (an EF strategy with "
                f"bucket_size set); got strategy={self.strategy!r}, "
                f"bucket_size={self.bucket_size!r}"
            )
        if self.byz is not None and (self.strategy == "dense" or self.bucket_size is None):
            raise PathConfigError(
                "byz fault injection / tolerance needs the bucketed EF path (the "
                "adversary owns lanes of the vmap'd worker axis); got "
                f"strategy={self.strategy!r}, bucket_size={self.bucket_size!r}"
            )
        from repro.obs.telemetry import TELEMETRY_CHOICES

        if self.telemetry not in TELEMETRY_CHOICES:
            raise PathConfigError(
                f"unknown telemetry level {self.telemetry!r}; options: {TELEMETRY_CHOICES}"
            )
        if self.telemetry != "off" and (self.strategy == "dense" or self.bucket_size is None):
            raise PathConfigError(
                "in-graph telemetry reads the bucketed aggregator's intermediates "
                "(per-group EF residuals / densities); it needs a bucketed strategy "
                f"with bucket_size set, got strategy={self.strategy!r}, "
                f"bucket_size={self.bucket_size!r}"
            )
        if self.fed is not None:
            if self.strategy == "dense" or self.bucket_size is None:
                raise PathConfigError(
                    "the federated tier consumes the bucketed EF wire format (per-"
                    "client residual pools are (n_clients, n_buckets, bucket_size) "
                    "stacks); it needs an EF strategy with bucket_size set, got "
                    f"strategy={self.strategy!r}, bucket_size={self.bucket_size!r}"
                )
            if self.strategy != "ef_allgather":
                raise PathConfigError(
                    "fed server aggregation is the payload-mean family: use "
                    f"strategy='ef_allgather' with a fed rider, got {self.strategy!r} "
                    "(ring/alltoall hop structure and the robust decodes have no "
                    "server-side analogue yet)"
                )
            if self.byz is not None:
                raise PathConfigError(
                    "byz × fed is not supported yet: client sampling turns the "
                    "declared tolerance into a per-round STOCHASTIC attacker count "
                    "(see ROADMAP); drop the byz rider or the fed rider"
                )
            if self.overlap is not None:
                raise PathConfigError(
                    "overlap pipelines the data-parallel collective with backward "
                    "compute; the fed round is a server-side simulation with no "
                    "collective to hide — drop the overlap rider"
                )
        if (
            self.strategy in collective.EF_STRATEGIES
            and self.bucket_size is not None
            and compressed.is_sign(comp)
            and jax.default_backend() == "tpu"
            and self.bucket_size % ops.BUCKET_PALLAS_MULTIPLE
        ):
            raise WireFormatError(
                f"on a TPU the sign wire format runs the Pallas bucket kernels, "
                f"which need bucket_size % {ops.BUCKET_PALLAS_MULTIPLE} == 0; got "
                f"{self.bucket_size}"
            )
        if ef_axes is not None and self.strategy == "ef_ring":
            backends.ring_axis(ef_axes)  # single-axis EF world required
        if world is not None:
            robust.validate_tolerance(self.strategy, self.byz_f, world)
        return self


def make_aggregator(
    spec: CommSpec,
    layout: bucketize.BucketLayout,
    mesh,
    ef_axes: AxisNames,
    *,
    params=None,
):
    """THE construction path for bucketed aggregators.

    Validates ``spec`` against the mesh, resolves the collective backend, and
    dispatches: ``spec.overlap`` set (and W > 1) builds the async-overlap
    pipelined aggregator — which needs the parameter tree (``params``) to
    derive the reverse-AD group schedule — otherwise the one-shot bucketed
    aggregator. Signature of the returned callable matches the legacy
    factories: ``fn(buckets_w, err_w, srv_w, key) -> (agg, new_err_w,
    new_srv_w, info)``.
    """
    from repro.comm import backends, collective

    w = collective.world_size(mesh, ef_axes)
    spec.validate(world=w, ef_axes=ef_axes)
    comp = spec.resolved_compressor
    backend = backends.resolve(spec, mesh, ef_axes, layout=layout)
    if spec.overlap is not None and w > 1:
        from repro.overlap import pipeline
        from repro.overlap import schedule as overlap_schedule

        if params is None:
            raise PathConfigError(
                "spec.overlap needs the parameter tree to derive the reverse-AD "
                "group schedule; pass params= to make_aggregator"
            )
        sched = overlap_schedule.build_schedule(
            layout, params, n_groups=spec.overlap.n_groups, comp=comp
        )
        return pipeline.build_overlapped_aggregator(
            spec.strategy,
            comp,
            layout,
            sched,
            mesh,
            ef_axes,
            backend=backend,
            telemetry=spec.telemetry == "full",
            byz_f=spec.byz_f,
        )
    return collective.build_bucketed_aggregator(
        spec.strategy,
        comp,
        layout,
        mesh,
        ef_axes,
        byz_f=spec.byz_f,
        backend=backend,
        telemetry=spec.telemetry == "full",
    )
