# Developer entry points. (The native store has its own Makefile under
# native/; `make -C native`.)

PY ?= python
NATIVE_SRCS := $(wildcard native/*.cc)

.PHONY: lint lint-native lint-fix-docs check test native native-sanitize

# graftlint over the package (all 9 families, including the
# whole-program protocol/lifecycle/lockgraph stage). Runs the
# standalone launcher under -S: skips site processing AND the ray_tpu
# package __init__, so a warm run (model cache under .graftlint_cache/)
# stays under ~1.5 s.
lint:
	$(PY) -S ray_tpu/devtools/graftlint/standalone.py

# compiler-as-linter over the native plane: syntax + warnings only,
# no objects produced (the real build is `make -C native`)
lint-native:
	$(CXX) -std=c++17 -fsyntax-only -Wall -Wextra $(NATIVE_SRCS)

# regenerate the README rule catalog after adding/changing rules
lint-fix-docs:
	$(PY) -S ray_tpu/devtools/graftlint/standalone.py --update README.md

# everything a PR must pass locally, cheapest first
check: lint lint-native test

test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow'

native:
	$(MAKE) -C native

# ASan/UBSan + TSan variants of the native plane plus the stress
# harnesses (see native/Makefile `sanitize`)
native-sanitize:
	$(MAKE) -C native sanitize
