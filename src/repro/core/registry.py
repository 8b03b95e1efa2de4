"""Solver registry — the framework's public sampling API.

Every solver is registered as a :class:`~repro.core.program.SolverProgram`
(the uniform compiled-sampling contract: scan entry + donatable buffers +
carry pspecs + request policy + default configs), so the serving engine can
fuse and route requests to any of them.  The classic functional surface is
kept on top:

    from repro.core import get_solver, SolverConfig
    out = get_solver("era")(eps_fn, x_T, schedule, ERAConfig(nfe=10, k=4))

    from repro.core import get_program
    program = get_program("ddim")          # the serving-engine surface
"""

from __future__ import annotations

from typing import Callable

from repro.core import adams, ddim, dpm_adaptive, dpm_solver, era
from repro.core.program import SolverProgram
from repro.core.solver_base import SolverConfig, SolverOutput

SampleFn = Callable[..., SolverOutput]

_PROGRAMS: dict[str, SolverProgram] = {
    # baselines the paper compares against
    "ddim": ddim.DDIMProgram(),
    "explicit_adams": adams.ExplicitAdamsProgram(),         # PNDM/FON family
    "implicit_adams_pece": adams.ImplicitAdamsPECEProgram(),
    "dpm_solver_2": dpm_solver.DPMSolverProgram(
        "dpm_solver_2", order=2, fast=False
    ),
    "dpm_solver_fast": dpm_solver.DPMSolverProgram(
        "dpm_solver_fast", order=3, fast=True
    ),
    "dpm_solver_pp2m": dpm_solver.DPMpp2MProgram(),
    "dpm_adaptive": dpm_adaptive.AdaptiveDPMProgram(),
    # the paper's contribution (+ its Table-4 "fixed" ablation)
    "era": era.ERAProgram(),
}


def get_program(name: str) -> SolverProgram:
    try:
        return _PROGRAMS[name]
    except KeyError:
        raise ValueError(
            f"unknown solver {name!r}; available: {sorted(_PROGRAMS)}"
        ) from None


def sample_program(dlm, schedule, solver: str, solver_config: SolverConfig):
    """The whole sampling loop as one jittable ``program(params, x_init)
    -> x0``: solver ``solver``'s ``sample`` over ``dlm.eps_fn(params)``.
    This is the program the multi-pod dry run lowers."""
    sample_fn = get_program(solver).sample

    def program(params, x_init):
        return sample_fn(dlm.eps_fn(params), x_init, schedule, solver_config).x0

    return program


def get_solver(name: str) -> SampleFn:
    """The classic functional entry: ``f(eps_fn, x_T, schedule, cfg)``."""
    return get_program(name).sample


def solver_names() -> list[str]:
    return sorted(_PROGRAMS)


def default_config(name: str, **kw) -> SolverConfig:
    return get_program(name).default_config(**kw)
