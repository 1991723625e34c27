"""Plain float32 reference of the registration solver's mathematics.

Written from the CLAIRE formulation (arXiv:2004.08893) and the discretization
the solver states: periodic grid on (0, 2*pi)^3, stationary velocity,
semi-Lagrangian transport with RK2 footpoints, cubic B-spline interpolation on
15-tap FIR-prefiltered coefficients, FD8 first derivatives, the spectral
H1-div regularizer, SSD distance. It imports nothing of the program: every
interpolation is a direct 64-tap evaluation at the footpoints, with no plans,
no caching across calls and no kernels.
"""

from .claire import (  # noqa: F401
    adjoint_solve,
    det_f,
    fd8_div,
    fd8_grad,
    fd8_partial,
    footpoints,
    gauss_smooth,
    gradient,
    index_grid,
    inner,
    interp,
    objective,
    prefilter,
    reg_apply,
    relative_mismatch,
    spacing,
    state_solve,
)
