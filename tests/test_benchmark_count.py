"""``serve_tok_s``'s count in the suite the driver runs (ROADMAP.md D14 (c)):
the six cases of ``benchmark/tests/test_serve_tok_s_count.py`` (pure numpy,
under two seconds), imported so that tier-1 holds the count's monotonicity:
it never falls as the engine's step shortens. The cases and their fixtures
stay where the benchmark keeps them; ``benchmark/tests/test_manifest.py`` is
not imported yet (ROADMAP.md W0 (3))."""

from benchmark.tests.test_serve_tok_s_count import (  # noqa: F401
    bound, man, test_the_count_never_falls_as_the_step_shortens,
    test_the_model_reads_what_the_issue_reckoned,
    test_the_rule_it_replaced_read_a_faster_engine_as_a_slower_one)
