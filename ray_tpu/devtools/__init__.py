"""Developer tooling that ships with the tree (linters, codegen).

Nothing here is imported by the runtime — keep it free of jax and of any
import with side effects so ``make lint`` stays cheap.
"""
