# Convenience targets. Nothing here is required at runtime: the native
# library auto-builds (and auto-skips) on first import, and every native
# consumer has a pure-Python/numpy fallback rung.

PYTHON ?= python

.PHONY: native test tier1 clean-native

# Build (or rebuild) the native library. Degrades, never errors: on a box
# without a C++ toolchain build.py prints a one-line skip reason and
# exits 0 — the fallback ladders (digest, chunker, io ring) carry on.
native:
	$(PYTHON) -m dragonfly2_tpu.native.build

# The tier-1 suite (what CI gates on).
test tier1:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/ -q -m 'not slow'

clean-native:
	$(PYTHON) -c "from dragonfly2_tpu.native import build; build.clean()"
