"""SPH substrate: the paper's physics + the task-based engine.

New code should enter through the API layer — ``SimulationSpec`` +
``build_simulation`` (``repro.sph.api``) — which compiles a frozen spec
into any of the {global, timebin} × {local, distributed} engines. The
engine classes (``Simulation``, ``TimeBinSimulation``,
``distributed.DistSimulation``) remain importable as the engine layer /
legacy shims.
"""

from ..observability import ObserveSpec, RunObserver
from .api import (SCENARIOS, SimulationSpec, build_simulation, make_ic,
                  register_scenario)
from .api import Simulation as SimulationProtocol
from .cellgrid import (GridSpec, PairList, ParticleCells, bin_particles,
                       build_pair_list, choose_grid, unbin)
from .engine import (SPHConfig, SPHState, Simulation, build_taskgraph,
                     cfl_timestep, compute_accelerations, init_state, step)
from .engine import cfl_timestep_particles
from .ic import (clustered_ic, kelvin_helmholtz_ic, sedov_ic, sodshock_ic,
                 uniform_ic)
from .physics import (GAMMA, cfl_timestep_block, density_block, eos_pressure,
                      force_block, ghost_update, smoothing_length_update,
                      sound_speed)
from .smoothing import dw_dh, get_kernel, w_cubic, w_wendland_c2
from .timebins import (TimeBinSimulation, TimeBinState, active_level,
                       assign_bins, bin_timestep, cell_bin_histogram,
                       cell_max_bins, timebin_init)
from .dist_timebins import (DistTimeBinSimulation, build_rank_plan,
                            halo_export_schedule)
from .collectives import (CollectiveTransport, build_allgather_program,
                          build_permute_program)

__all__ = [
    "SCENARIOS", "SimulationSpec", "SimulationProtocol", "build_simulation",
    "make_ic", "register_scenario", "ObserveSpec", "RunObserver",
    "GridSpec", "PairList", "ParticleCells", "bin_particles",
    "build_pair_list", "choose_grid", "unbin",
    "SPHConfig", "SPHState", "Simulation", "build_taskgraph", "cfl_timestep",
    "cfl_timestep_particles", "compute_accelerations", "init_state", "step",
    "clustered_ic", "kelvin_helmholtz_ic", "sedov_ic", "sodshock_ic",
    "uniform_ic",
    "GAMMA", "cfl_timestep_block", "density_block", "eos_pressure",
    "force_block", "ghost_update", "smoothing_length_update", "sound_speed",
    "dw_dh", "get_kernel", "w_cubic", "w_wendland_c2",
    "TimeBinSimulation", "TimeBinState", "active_level", "assign_bins",
    "bin_timestep", "cell_bin_histogram", "cell_max_bins", "timebin_init",
    "DistTimeBinSimulation", "build_rank_plan", "halo_export_schedule",
    "CollectiveTransport", "build_allgather_program",
    "build_permute_program",
]
