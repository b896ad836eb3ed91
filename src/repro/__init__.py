"""repro: Thread Coarsening on TPU — JAX/Pallas training & serving framework.

The paper's contribution (thread coarsening vs pipeline replication vs SIMD
vectorization) lives in `repro.core` + `repro.kernels`; the production
substrate (models, data, optim, checkpoint, runtime, distributed, launch)
makes it deployable at multi-pod scale.  See DESIGN.md.
"""
