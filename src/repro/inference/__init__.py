"""Causal significance subsystem (DESIGN.md SS9): turns raw rho maps
into statistically validated causal graphs — one-sweep convergence CCM
over prefix-snapshot kNN tables, batched surrogate null models, and
FDR-controlled significance masking, scaled with the phase-2 machinery.
"""
from repro.inference.convergence import (
    ccm_convergence_pair,
    convergence_stats,
    subsample_permutation,
)
from repro.inference.pipeline import (
    SignificanceChunkRunner,
    finalize_significance,
    run_sig_chunks,
    run_significance,
)
from repro.inference.significance import (
    assemble_edges,
    bh_adjust,
    bh_threshold,
    bh_threshold_discrete,
)
from repro.inference.surrogates import (
    phase_randomized,
    random_shuffle,
    surrogate_futures,
)
from repro.inference.types import (
    EDGE_DTYPE,
    SignificanceConfig,
    SignificanceResult,
)

__all__ = [
    "EDGE_DTYPE",
    "SignificanceChunkRunner",
    "SignificanceConfig",
    "SignificanceResult",
    "assemble_edges",
    "finalize_significance",
    "bh_adjust",
    "bh_threshold",
    "bh_threshold_discrete",
    "ccm_convergence_pair",
    "convergence_stats",
    "phase_randomized",
    "random_shuffle",
    "run_sig_chunks",
    "run_significance",
    "subsample_permutation",
    "surrogate_futures",
]
