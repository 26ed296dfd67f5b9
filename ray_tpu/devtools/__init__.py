"""Developer tooling that ships with the tree (linters, codegen, the serve
step program's lowered text: ``step_text``).

Nothing here is imported by the runtime — keep it free of jax at import
(``step_text`` imports it where it lowers) and of any import with side
effects so ``make lint`` stays cheap.
"""
