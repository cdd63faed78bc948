from repro.kernels.ell_relax.degree import (DegreeBuckets, degree_buckets,
                                            sweep_degree)
from repro.kernels.ell_relax.ell_relax import ell_relax, ell_relax_windowed
from repro.kernels.ell_relax.layout import (DEFAULT_VMEM_BUDGET,
                                            VMEM_BUDGET_ENV_VAR, BucketedEll,
                                            WindowPlan, build_bucketed_ell,
                                            clear_layout_cache, kernel_fits,
                                            max_window, sweep_layout,
                                            vmem_budget, window_plan)
from repro.kernels.ell_relax.ops import (ELL_RELAX_ENV_VAR, ell_sweep,
                                         reset_warnings, resolve_sweep_backend,
                                         resolve_use_kernel, sweep_form,
                                         warn_vmem_fallback)
from repro.kernels.ell_relax.ref import ell_sweep_bucketed_ref, ell_sweep_ref

__all__ = [
    "ell_relax", "ell_relax_windowed",
    "ell_sweep", "ell_sweep_ref", "ell_sweep_bucketed_ref",
    "resolve_use_kernel", "resolve_sweep_backend", "sweep_form",
    "kernel_fits", "max_window", "vmem_budget", "window_plan",
    "sweep_layout", "build_bucketed_ell", "clear_layout_cache",
    "BucketedEll", "WindowPlan",
    "DegreeBuckets", "degree_buckets", "sweep_degree",
    "ELL_RELAX_ENV_VAR", "VMEM_BUDGET_ENV_VAR", "DEFAULT_VMEM_BUDGET",
    "warn_vmem_fallback",
    "reset_warnings",
]
