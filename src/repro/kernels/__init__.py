"""Pallas TPU kernels for the CR-spline activation unit.

Layout (the spline-epilogue subsystem):
  epilogue.py   the ONE in-kernel CR datapath + composable epilogues
                (tanh/sigmoid/silu/gelu_tanh/softplus) and both kernel
                builders (element-wise, fused GLU)
  cr_act.py     thin matmul-free instance (act="tanh") — back-compat
  fused_glu.py  thin GLU instance — back-compat
  ops.py        jit'd public wrappers: padding, leading dims, custom-VJP
                recompute backward
  ref.py        pure-jnp oracles the kernels are validated against
"""
