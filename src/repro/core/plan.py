"""Shared compile-then-execute base for the four MatPIM algorithm plans.

A plan owns a crossbar geometry, a generated ``Program``, and the data
layout that maps operands into crossbar cells. :class:`CrossbarPlan` adds the
compiled-execution machinery on top:

    plan.compile()                      -> CompiledProgram (cached, validated)
    plan.execute(mem, backend=...)      -> final memory, one crossbar
    plan.execute_batch(mems, ...)       -> EngineResult over B crossbars

``backend`` is one of:

    "interp" — the legacy per-op Python interpreter (``Crossbar.run``);
               validates every cycle as it executes.
    "numpy"  — vectorized bit-plane executor (default; replays the fused
               macro-op schedule — exactly equal memory/cycles/stats).
    "jax"    — jitted executor; fused segment lowering where eligible, else
               the per-cycle ``lax.scan``. Fast for single instances *and*
               batched (tiled / multi-instance) simulation.

plus the explicit ``-fused`` / ``-unfused`` variants of the compiled
backends (see ``engine.execute``). ``compile(fuse=True)`` is the default:
every compiled trace carries its macro-op ``FusedSchedule``; pass
``fuse=False`` to study the unfused trace (executors then use per-cycle
replay unless a fused variant is requested explicitly).

The compile cache is invalidated whenever ``self.program`` is rebound (the
conv plans regenerate their program when the kernel changes).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

from .compile import CompiledProgram, compile_program
from .crossbar import Crossbar
from .engine import EngineResult, execute


class CrossbarPlan:
    """Mixin/base: subclasses set ``rows``, ``cols``, ``parts`` and
    ``self.program`` (a list of cycles) before calling the methods here.

    The compile→execute flow shared by all four algorithm plans:

    >>> from repro.core import BinaryMatvecPlan
    >>> plan = BinaryMatvecPlan(2, 8, rows=16, cols=64, parts=2)
    >>> mem = np.zeros((16, 64), dtype=np.uint8)
    >>> plan.load_into(mem, np.ones((2, 8)), np.ones(8))
    >>> out, cycles, stats = plan.execute(mem)       # compiled numpy backend
    >>> cycles == plan.cycles == plan.compile().n_cycles
    True
    >>> plan.energy().cycles == cycles               # static trace pricing
    True
    """

    rows: int
    cols: int
    parts: int
    program: Optional[list]

    _compiled: Optional[CompiledProgram] = None
    _compiled_src: Optional[list] = None

    # -- compilation ---------------------------------------------------------

    def compile(self, validate: bool = True,
                fuse: bool = True) -> CompiledProgram:
        prog = self.program
        assert prog is not None, "plan has no program built yet"
        if self._compiled is None or self._compiled_src is not prog:
            self._compiled = compile_program(
                prog, self.rows, self.cols, self.parts, self.parts,
                validate=validate, fuse=fuse)
            self._compiled_src = prog
            self._compiled.pallas_spec = self.pallas_spec()
        elif fuse and self._compiled.schedule is None:
            from .compile import fuse_program
            self._compiled.schedule = fuse_program(self._compiled)
        elif not fuse and self._compiled.schedule is not None:
            # honor the explicit request for an unfused trace without
            # clobbering the fused cache other callers rely on
            cp = compile_program(
                prog, self.rows, self.cols, self.parts, self.parts,
                validate=validate, fuse=False)
            cp.pallas_spec = self.pallas_spec()
            return cp
        return self._compiled

    def _trace(self) -> CompiledProgram:
        """The trace as compiled: a ``compile(fuse=False)`` trace is run as
        it is, so the engine replays it unfused."""
        if self._compiled is not None and self._compiled_src is self.program:
            return self._compiled
        return self.compile()

    def adopt_compiled(self, cp: CompiledProgram) -> CompiledProgram:
        """Install a deserialized trace as this plan's :meth:`compile` result.

        The restore half of ``core.compile.compiled_state`` — a plan-store
        hit calls this instead of recompiling. Geometry must match the plan
        (a mismatched trace raises ``ValueError`` and the caller recompiles);
        the pallas layout manifest is derived state, reattached here rather
        than serialized. Requires ``self.program`` to be built already so
        the usual rebind-invalidation rule (conv kernels) keeps working.
        """
        prog = self.program
        assert prog is not None, "plan has no program built yet"
        if (cp.rows, cp.cols) != (self.rows, self.cols):
            raise ValueError(
                f"compiled trace geometry {(cp.rows, cp.cols)} != plan "
                f"geometry {(self.rows, self.cols)}")
        cp.pallas_spec = self.pallas_spec()
        self._compiled = cp
        self._compiled_src = prog
        return cp

    def pallas_spec(self):
        """Layout manifest for the pallas executor backend, or ``None``.

        Algorithm plans that the ``repro.kernels`` tri can compute override
        this (see ``core.pallas_exec``); the default keeps arbitrary
        programs on the replay backends.
        """
        return None

    @property
    def cycles(self) -> int:
        return len(self.program)

    def clear_caches(self) -> None:
        """Drop the compiled trace's executor memoizations (replay plans,
        jitted runners). The compiled trace itself stays cached; execution
        after this call rebuilds the runners on demand."""
        if self._compiled is not None:
            self._compiled.clear_caches()

    # -- device models -------------------------------------------------------

    def energy(self, profile=None):
        """Switching-energy/EDP report for this plan's compiled trace.

        ``profile`` is a :class:`repro.device.energy.DeviceProfile`, a
        profile name, or ``None`` (VTEAM-like default). Static accounting:
        derived from the trace's write masks, no execution needed.
        """
        from ..device.energy import trace_energy
        return trace_energy(self.compile(), profile)

    # -- execution -----------------------------------------------------------

    def new_crossbar(self) -> Crossbar:
        return Crossbar(self.rows, self.cols, self.parts, self.parts)

    def execute(
        self,
        mem: np.ndarray,
        xbar: Optional[Crossbar] = None,
        backend: str = "numpy",
        faults=None,
        rng=None,
    ) -> Tuple[np.ndarray, int, Dict[str, int]]:
        """Run this plan's program over one crossbar image ``mem``.

        Returns (final mem, cycle count, stats). Passing ``xbar`` forces the
        interpreter path on that crossbar object (legacy API), replacing its
        memory with ``mem`` and resetting its cycle/stat counters — every
        call reports THIS run's accounting, exactly like the compiled
        backends and the batched interpreter path, however often the
        crossbar is reused. ``faults``/``rng`` select a stochastic device
        model (compiled backends only; see ``engine.execute``).
        """
        if xbar is not None or backend == "interp":
            self._reject_interp_faults(faults)
            xb = xbar or self.new_crossbar()
            xb.mem[:, :] = mem
            xb.cycles = 0
            xb.stats = {k: 0 for k in xb.stats}
            xb.run(self.program)
            return xb.mem, xb.cycles, dict(xb.stats)
        res = execute(self._trace(), mem, backend=backend, faults=faults,
                      rng=rng)
        return res.mem, res.cycles, res.stats

    @staticmethod
    def _reject_interp_faults(faults) -> None:
        if faults is not None and not faults.is_ideal:
            raise ValueError("fault injection requires a compiled backend "
                             "('numpy' or 'jax'), not the interpreter")

    def run_program(
        self,
        loader,
        xbar: Optional[Crossbar] = None,
        backend: str = "numpy",
    ) -> Tuple[np.ndarray, int, Dict[str, int]]:
        """Shared ``run()`` body: load operands, execute, return final state.

        ``loader(mem)`` writes only the operand cells. With a caller-supplied
        ``xbar`` the loader applies to its EXISTING memory (preserving any
        other state the caller staged there, as the legacy drivers did) and
        the interpreter runs on it; cycle/stat counters reset per call —
        memory is the only state that survives reuse, exactly as in
        :meth:`execute`. Otherwise a fresh zeroed image goes through the
        selected backend.
        """
        if xbar is not None:
            loader(xbar.mem)
            xbar.cycles = 0
            xbar.stats = {k: 0 for k in xbar.stats}
            xbar.run(self.program)
            return xbar.mem, xbar.cycles, dict(xbar.stats)
        mem = np.zeros((self.rows, self.cols), dtype=np.uint8)
        loader(mem)
        return self.execute(mem, None, backend)

    def execute_batch(
        self,
        mems: np.ndarray,
        backend: str = "numpy",
        max_batch: Optional[int] = None,
        faults=None,
        rng=None,
        mesh=None,
    ) -> EngineResult:
        """Run this plan's program over ``(B, rows, cols)`` crossbars at once.

        ``backend="interp"`` loops the legacy interpreter over the batch
        (slow; useful for equivalence checks of batched/tiled paths).
        With ``faults``, every crossbar in the batch draws an independent
        fault realization — the Monte-Carlo axis of ``repro.device``.
        ``mesh`` (or an ambient ``distributed.sharding.use_mesh``) shards the
        batch axis over a jax device mesh — see ``distributed.mesh_exec``.
        """
        if backend == "interp":
            self._reject_interp_faults(faults)
            out = np.empty_like(mems)
            xb = self.new_crossbar()
            for b in range(mems.shape[0]):
                xb.mem[:, :] = mems[b]
                xb.cycles = 0
                xb.stats = {k: 0 for k in xb.stats}
                xb.run(self.program)
                out[b] = xb.mem
            return EngineResult(mem=out, cycles=xb.cycles,
                                stats=dict(xb.stats), backend="interp")
        return execute(self._trace(), mems, backend=backend,
                       max_batch=max_batch, faults=faults, rng=rng,
                       mesh=mesh)
