"""ERA-Solver core: diffusion ODE solvers (the paper's contribution).

Public API:
    get_solver(name)                 -> sampling function
    get_program(name)                -> SolverProgram (the serving surface)
    sample_program(dlm, ...)         -> one jittable params, x_T -> x0 program
    SolverConfig / ERAConfig         -> solver options
    NoiseSchedule / get_schedule     -> VP noise schedules
    timesteps                        -> solver time grids
"""

from repro.core.dpm_adaptive import AdaptiveDPMConfig
from repro.core.era import ERAConfig, era_combine
from repro.core.program import SolverProgram
from repro.core.registry import (
    default_config,
    get_program,
    get_solver,
    sample_program,
    solver_names,
)
from repro.core.schedules import (
    NoiseSchedule,
    cosine_schedule,
    get_schedule,
    linear_schedule,
    timesteps,
)
from repro.core.solver_base import SolverConfig, SolverOutput, ddim_step

__all__ = [
    "AdaptiveDPMConfig",
    "ERAConfig",
    "NoiseSchedule",
    "SolverConfig",
    "SolverOutput",
    "SolverProgram",
    "cosine_schedule",
    "ddim_step",
    "default_config",
    "era_combine",
    "get_program",
    "get_schedule",
    "get_solver",
    "linear_schedule",
    "sample_program",
    "solver_names",
    "timesteps",
]
