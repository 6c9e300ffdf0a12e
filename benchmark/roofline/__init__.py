"""The work each kernel family needs, from the data and the queries sent:
``work(w) -> (operations, bytes)``; ``PREFIXES`` name the family's
jitted programs (``jit_<prefix>...`` modules in the trace)."""
