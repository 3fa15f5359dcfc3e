# Developer entry points.  The native C++ graph builders have their own
# Makefile (native/); this one fronts the python-side checks.

PY ?= python

.PHONY: lint test native

# gossip-lint: the AST contract checker (docs/STATIC_ANALYSIS.md).
# Exit 0 = every finding baselined-with-justification, no stale
# suppressions.  Runs in ~a second — cheap enough for every edit loop.
lint:
	$(PY) -m p2p_gossipprotocol_tpu.analysis

# tier-1 (ROADMAP.md has the canonical pinned invocation)
test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow' \
		--continue-on-collection-errors -p no:cacheprovider

native:
	$(MAKE) -C native
