"""Plain references for the benchmark's correctness check.

They import nothing of the program and take nothing it made: weights and
data come from the benchmark's own generators, and every table (the
Hadamard matrix, the estimator's normalisation constant) is computed here.
What they share with the program is the semantics: the parameter layout,
the key derivation of the estimators' random draws, the estimators' decode
equations and AdamW's update, each written out below in plain ``jax.numpy``
at float32 with ``Precision.HIGHEST``.
"""
