"""Test-session setup shared by every test module.

The kernel-vs-oracle tests compare results bit for bit. On an x86 host
with FMA, XLA's CPU backend contracts ``a * b + c`` into a fused
multiply-add in some fusions and not in others, depending on how the
surrounding program is fused — so the same Adam moment update can round
differently in the interpreted kernel and in the jitted oracle. Capping
the CPU code generator at AVX (no FMA instructions) makes every fusion
round each product and each sum on its own, as the kernels' op order
specifies. It must be set before JAX initialises its CPU backend.
"""
import os

_FLAG = "--xla_cpu_max_isa=AVX"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                               + _FLAG).strip()
