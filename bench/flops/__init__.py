"""Model FLOPs of one training row (one sequence, forward and backward),
one module per model family, named by a configuration's ``family`` key.
Each counts the multiply-adds of the matmuls and of the attention or state
space contractions the model requires, two FLOPs each, three passes
(forward, and the two halves of the backward). Recomputed work (remat) is
not counted, nor elementwise work, norms, softmax or embedding gathers."""
