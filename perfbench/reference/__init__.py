"""Plain references: numpy and float64 only, nothing of the program.

One module per ``"reference"`` name an operation file gives.  Each has

- ``make_input(n, seed)``: the float32 input, O(n^2) on the host: one
  array where the operation has one operand, a dict of arrays by
  operand name (``operations/<op>.json``: ``operands``) where it has
  several;
- ``expected(M, seed)``: what the check holds a result against (seeded
  vectors and the input applied to them), once per run; ``M`` is what
  ``make_input`` returned;
- ``residual(result, exp)``: the number compared with the
  configuration's limit, float64 in row blocks so that no n x n float64
  copy is ever made; ``result`` is the array of the one operand the
  call writes (a dict by name where it writes several);
- ``plain_factor(M, nb, precision)`` (``plain_product`` where the
  operation is no factorization): the same operation written plainly,
  tile loop by tile loop, with the matmul operands rounded as the chip
  would round them at that precision; the control of the ``correct``
  comparison in ``checks/`` runs it in the program's place.
"""
