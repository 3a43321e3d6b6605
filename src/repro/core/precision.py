"""Precision of the digital dots: full f32 on every backend.

A TPU's default f32 dot is one bf16 pass (~3 significant digits).  Left
at the default, the host preprocessor (Schur complements, IR-drop terms),
the arena cascade and the Pallas kernels' tile dots read 2e-3 to 5e-3
from a float64 run on a TPU v5e, where the CPU reads ~3e-7.  Every dot on
the serving path passes `precision=F32_DOT`; XLA:CPU ignores the setting,
so CPU results do not change.
"""
import jax

F32_DOT = jax.lax.Precision.HIGHEST
