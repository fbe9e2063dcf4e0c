"""One file a model family (a configuration's ``family`` key, ``ctr``
where there is none): the pool, the weights, what is compared, the plain
reference's pass, the numbers and the step's work (``ctr.py`` says what
each function takes and returns)."""
