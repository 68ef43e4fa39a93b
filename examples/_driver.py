"""Shared scaffolding for the example drivers (analog of the repeated
cfg -> vanilla -> WheelSpinner preamble in every reference example,
e.g. reference examples/sizes/sizes_cylinders.py:20-70).

Each per-model driver declares the standard flag groups, delegates to
the Amalgamator (EF mode or cylinders mode), and prints the bounds —
so `run_all.py` can smoke every family with real command lines.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))          # repo root, for mpisppy_tpu

from mpisppy_tpu.utils.platform import (  # noqa: E402
    enable_compile_cache, enable_f64_if_cpu, ensure_cpu_backend)

ensure_cpu_backend()        # no-op unless JAX_PLATFORMS requests cpu
enable_f64_if_cpu()         # CPU runs follow the f64 protocol
enable_compile_cache()      # repeat runs skip ~30 s of compiles

from mpisppy_tpu.utils import amalgamator, config  # noqa: E402


def standard_cfg():
    cfg = config.Config()
    cfg.popular_args()
    cfg.ph_args()
    cfg.two_sided_args()
    cfg.fwph_args()
    cfg.lagrangian_args()
    cfg.lagranger_args()
    cfg.xhatlooper_args()
    cfg.xhatshuffle_args()
    cfg.xhatspecific_args()
    cfg.xhatxbar_args()
    cfg.xhatlshaped_args()
    cfg.slammax_args()
    cfg.slammin_args()
    cfg.fixer_args()
    cfg.gapper_args()
    cfg.converger_args()
    cfg.norm_rho_args()
    cfg.mult_rho_args()
    cfg.wtracker_args()
    cfg.ef_args()
    return cfg


def cylinders_main(module, progname, args=None, extraargs_fct=None):
    """Parse the standard flag surface and run the model through the
    Amalgamator.  Returns the Amalgamator (bounds on
    .best_inner_bound/.best_outer_bound, or .EF_Obj in --EF mode).

    Prints one machine-readable `DRIVER_WALL build=..s run=..s` line —
    run_all.py records the split so corpus timings separate problem
    construction from the solve loop (whose first iteration carries
    the jit compiles)."""
    cfg = standard_cfg()
    if extraargs_fct is not None:
        extraargs_fct(cfg)
    ama = amalgamator.from_module(module, cfg, use_command_line=True,
                                  args=args, progname=progname)
    ama.run()
    if ama.is_EF:
        print(f"EF objective = {ama.EF_Obj}")
    else:
        print(f"BestInnerBound = {ama.best_inner_bound}")
        print(f"BestOuterBound = {ama.best_outer_bound}")
    print(f"DRIVER_WALL build={ama.wall_build:.2f}s "
          f"run={ama.wall_run:.2f}s")
    return ama
