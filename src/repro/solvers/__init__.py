"""Krylov solver subsystem (DESIGN.md §7): fully-jitted single-device and
``shard_map``-distributed PCG / block-CG / restarted GMRES(m), plus the
sharded geometric-multigrid V-cycle preconditioner."""
from .krylov import (PCGState, SolveResult, STATUS_BREAKDOWN,
                     STATUS_INDEFINITE, STATUS_NAN, STATUS_OK,
                     STATUS_STAGNATION, block_cg, gmres,
                     guards_enabled, pcg, pcg_init, pcg_segment,
                     set_guards_enabled)
from .mg import GridMG, MGArrays, build_grid_mg, mg_halo_bytes, \
    mg_precond_local, mg_specs, solver_hide_flops
from .distributed import (krylov_comm_bytes, make_dist_krylov,
                          make_dist_krylov_segment, pcg_state_specs,
                          result_specs)

__all__ = [
    "SolveResult", "pcg", "block_cg", "gmres",
    "PCGState", "pcg_init", "pcg_segment", "pcg_state_specs",
    "STATUS_OK", "STATUS_NAN", "STATUS_INDEFINITE", "STATUS_STAGNATION",
    "STATUS_BREAKDOWN", "guards_enabled", "set_guards_enabled",
    "GridMG", "MGArrays", "build_grid_mg", "mg_precond_local", "mg_specs",
    "mg_halo_bytes", "solver_hide_flops", "make_dist_krylov",
    "make_dist_krylov_segment", "krylov_comm_bytes", "result_specs",
]
