"""repro — Parallel Space-Time Kernel Density Estimation on TPU pods.

A production-grade JAX framework reproducing Saule et al. (2017) and
re-architecting its algorithms (PB-SYM + DR/DD/PD/SCHED/REP parallel
strategies) for multi-pod TPU meshes, embedded in a full training/serving
substrate.
"""
__version__ = "1.0.0"
