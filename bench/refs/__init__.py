"""Plain ``jax.numpy`` references. They import nothing of the program.

Each takes a ``cast`` for its contractions' operands: the identity for the
reference itself, at "highest" matmul precision, and a rounding to a lower
precision for the control that must come out as not correct.
"""
