"""ShardedEngine: the request engine lowered onto a ``data x model`` mesh.

The slot state ``[N, F, D]`` is the shard-friendly layout ROADMAP promised:
per-request done/budget masks are elementwise and every sweep op is either
row-local or a row-batched contraction, so the *same*
:func:`repro.core.factorizer.make_resonator` closures run under ``shard_map``
with rows split over ``data``.  Codebooks either replicate (pure
data-parallel serving) or shard their rows over ``model``
(``codebook_placement="rows"``), in which case the resonator is built in its
model-sharded mode — local-row similarity scores gathered with one packed
psum per factor (see factorizer docs for the exactness contract).

Host-side continuous batching (queueing, slot ownership, retirement) is
inherited unchanged from :class:`repro.engine.Engine`; only the three device
programs and the state placement differ.  The sweep-burst while_loop's
condition psums the live-row count over ``data`` so every shard runs the
same trip count (a diverged shard would deadlock the model-axis collectives
inside the sweep).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.cogsim import model as hw_model
from repro.core import factorizer as fz
from repro.core.quantization import QTensor
from repro.engine.engine import Engine, derive_sweeps_per_step
from repro.engine.registry import ServeSpec
from repro.engine.sharding.autotune import choose_slots
from repro.launch import mesh as launch_mesh

PLACEMENTS = ("replicated", "rows")


class ShardedEngine(Engine):
    """``Engine`` on a mesh: rows over ``data``, codebooks per placement.

    ``slots`` is the GLOBAL slot count (must divide by the data axis);
    leave it ``None`` to let :func:`choose_slots` pick slots-per-shard from
    the adSCH cost model and ``arrival_rps``.
    """

    engine_kind = "sharded_factorizer"

    def __init__(self, spec: ServeSpec, *, mesh=None,
                 codebook_placement: str = "replicated",
                 slots: int | None = None, arrival_rps: float | None = None,
                 sweeps_per_step: int | None = None, hw=hw_model.COGSYS,
                 key: jax.Array | None = None, fused=None, obs=None,
                 clock=None):
        self.mesh = mesh if mesh is not None else launch_mesh.make_host_mesh()
        for ax in ("data", "model"):
            if ax not in self.mesh.shape:
                raise ValueError(f"ShardedEngine needs a {ax!r} mesh axis; "
                                 f"got {dict(self.mesh.shape)}")
        self.data_shards = self.mesh.shape["data"]
        self.model_shards = self.mesh.shape["model"]
        if codebook_placement not in PLACEMENTS:
            raise ValueError(f"codebook_placement must be one of {PLACEMENTS}")
        self.codebook_placement = codebook_placement
        self._rows = codebook_placement == "rows" and self.model_shards > 1
        if codebook_placement == "rows":
            if isinstance(spec.codebooks, QTensor):
                raise ValueError("rows placement needs dense codebooks")
            M = spec.codebooks.shape[1]
            if M % self.model_shards:
                raise ValueError(
                    f"rows placement needs the model axis size "
                    f"({self.model_shards}) to divide the codebook rows ({M})")
        if slots is None:
            slots = self.data_shards * choose_slots(
                spec, arrival_rps=arrival_rps, data_shards=self.data_shards,
                model_shards=self.model_shards if self._rows else 1, hw=hw)
        if slots % self.data_shards:
            raise ValueError(f"the data axis size ({self.data_shards}) must "
                             f"divide slots ({slots})")
        super().__init__(spec, slots=slots, sweeps_per_step=sweeps_per_step,
                         hw=hw, key=key, fused=fused, obs=obs, clock=clock)

    # -- seams over the base engine ---------------------------------------

    def _derive_sweeps_per_step(self) -> int:
        return derive_sweeps_per_step(
            self.spec, self.slots, self.hw, data_shards=self.data_shards,
            model_shards=self.model_shards if self._rows else 1)

    def _build_programs(self) -> None:
        spec, mesh, slots = self.spec, self.mesh, self.slots
        cfg, mask = spec.cfg, spec.valid_mask
        n_loc = slots // self.data_shards
        rows = self._rows

        cb = spec.codebooks
        fused = self.fused
        if rows:
            M = cb.shape[1]
            init_est = fz.superposition_init(cb, cfg, mask)
            cb_spec = P(None, "model", None)  # [F, M, D] rows over `model`

            def make_rs(cb_arg):
                # fused-eligible cfgs run the shard-aware fused kernel here:
                # local matmuls fused, still one packed psum per factor
                return fz.make_resonator(cb_arg, cfg, mask,
                                         model_axis="model", full_rows=M,
                                         init_est=init_est, fused=fused)
        else:
            cb_spec = jax.tree.map(lambda _: P(), cb)  # replicated (QTensor ok)

            def make_rs(cb_arg):
                return fz.make_resonator(cb_arg, cfg, mask, fused=fused)

        state_spec = fz._State(est=P("data"), iters=P("data"), done=P("data"),
                               sim=P("data"), keys=P("data"), it=P())
        self._cb = jax.device_put(
            cb, jax.tree.map(lambda sp: NamedSharding(mesh, sp), cb_spec,
                             is_leaf=lambda x: isinstance(x, P)))

        def sweeps_body(cb_arg, qs, s, budget):
            rs = make_rs(cb_arg)

            def live(s):  # global live-row count -> uniform trip counts
                return jax.lax.psum(
                    jnp.sum(rs.active(s).astype(jnp.int32)), "data")

            def cond(c):
                _, n, alive = c
                return jnp.logical_and(n < budget, alive > 0)

            def body(c):
                s, n, _ = c
                s = rs.sweep(qs, s)
                return s, n + 1, live(s)

            s, n, _ = jax.lax.while_loop(cond, body,
                                         (s, jnp.int32(0), live(s)))
            return s, n

        def refill_body(cb_arg, qs, s, idx, new_qs, keys):
            rs = make_rs(cb_arg)
            # global slot ids -> local rows; out-of-shard ids hit the n_loc
            # sentinel and are dropped by refill_many's scatter (same
            # mechanism the host-side padding already relies on)
            li = idx.astype(jnp.int32) - jax.lax.axis_index("data") * n_loc
            li = jnp.where((li >= 0) & (li < n_loc), li, n_loc)
            return rs.refill_many(qs, s, li, new_qs, keys)

        def decode_body(cb_arg, qs, s):
            return make_rs(cb_arg).decode(qs, s)

        res_spec = fz.FactorizerResult(*([P("data")] * 5))
        _sweeps = jax.jit(jax.shard_map(
            sweeps_body, mesh=mesh,
            in_specs=(cb_spec, P("data"), state_spec, P()),
            out_specs=(state_spec, P()), check_vma=False))
        _refill = jax.jit(jax.shard_map(
            refill_body, mesh=mesh,
            in_specs=(cb_spec, P("data"), state_spec, P(), P(), P()),
            out_specs=(P("data"), state_spec), check_vma=False))
        _decode = jax.jit(jax.shard_map(
            decode_body, mesh=mesh,
            in_specs=(cb_spec, P("data"), state_spec),
            out_specs=res_spec, check_vma=False))
        self._sweeps = lambda qs, s, budget: _sweeps(self._cb, qs, s, budget)
        self._refill_many = lambda qs, s, *a: _refill(self._cb, qs, s, *a)
        self._decode = lambda qs, s: _decode(self._cb, qs, s)

        # Parked initial state, identical values to the single-device engine,
        # placed row-sharded over `data`.
        rs0 = fz.make_resonator(cb, cfg, mask)
        self._rs = rs0
        qs0 = jnp.zeros((slots, spec.dim), jnp.float32)
        st = rs0.init(qs0, jax.random.split(jax.random.PRNGKey(0), slots))
        st = st._replace(done=jnp.ones(slots, bool))
        put = lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp))
        self.qs = put(qs0, P("data"))
        self.state = jax.tree.map(put, st, state_spec,
                                  is_leaf=lambda x: isinstance(x, P))
        self._record_structure()

    def _psums_per_sweep(self) -> int:
        """The documented collectives contract per sweep iteration: one
        live-count psum over ``data``, plus one packed psum per factor when
        the codebook rows are sharded over ``model``."""
        if self._rows:
            return self.spec.codebooks.shape[0] + 1
        return 1

    def resize(self, slots: int) -> None:
        """Warm handoff re-tune (see :meth:`Engine.resize`); the new global
        slot count must still tile over the data axis."""
        if slots % self.data_shards:
            raise ValueError(f"resize({slots}) must divide by the data axis "
                             f"size ({self.data_shards})")
        super().resize(slots)

    def recover(self) -> int:
        """Fault recovery on the mesh (see :meth:`Engine.recover`): the
        inherited replay path runs through THIS class's ``_build_programs``,
        so the rebuild re-lowers the shard_map programs, re-places the
        codebooks per ``codebook_placement``, and re-shards the fresh parked
        state over ``data`` — a recovered mesh engine replays its in-flight
        rows under exactly the collectives contract it was serving with
        (one packed psum per factor for rows placement), keeping the replay
        bit-equal to the single-device engine's."""
        return super().recover()

    def snapshot(self, reset: bool = False) -> dict:
        st = super().snapshot(reset)
        st.update({"mesh": dict(self.mesh.shape),
                   "codebook_placement": self.codebook_placement,
                   "slots_per_shard": self.slots // self.data_shards})
        return st
