"""Plain references: numpy and float64 only, nothing of the program.

One module per ``"reference"`` name an operation file gives.  Each has

- ``make_input(n, seed)``: the float32 input, O(n^2) on the host;
- ``expected(M, seed)``: what the check holds a factor against (seeded
  vectors and the input applied to them), once per run;
- ``residual(factor, exp)``: the number compared with the
  configuration's limit, float64 in row blocks so that no n x n float64
  copy is ever made;
- ``plain_factor(M, nb, precision)``: the same factorization written
  plainly, tile loop by tile loop, with the matmul operands rounded as
  the chip would round them at that precision; the control of the
  ``correct`` comparison in ``checks/`` runs it in the program's place.
"""
