"""Shared numerical constants for ops kernels."""

# Large-negative instead of -inf for masking: keeps softmax NaN-free on
# fully-masked rows and is safely representable in f32. Shared by attention
# masking and sampler logit masking so the semantics can't diverge.
NEG_INF = -1e30
