# Dev-flow entry points.  Same commands CI runs — a green `make lint
# test-quick` locally means a green tier-1.
#
#   make lint        the ruff gate (correctness subset E9/F63/F7/F82;
#                    loud failure if ruff is installed but broken, skip
#                    only on a genuinely ruff-less image) + both
#                    tcdp-lint passes at zero findings
#   make lint-diff   pre-commit path: lint only files changed vs REV
#   make test-quick  the ~90 s iteration tier (pytest -m quick)
#   make test        full tier-1 (everything not marked slow)
#   make postmortem  DIR=<shared run dir>: merge blackbox bundles and
#                    print the root-cause verdict

PY ?= python
REV ?= HEAD~1

.PHONY: lint lint-diff test test-quick postmortem

lint:
	$(PY) -m pytest tests/test_lint.py::test_ruff_gate -q
	$(PY) tools/tcdp_lint.py

lint-diff:
	$(PY) -m pytest tests/test_lint.py::test_ruff_gate -q
	$(PY) tools/tcdp_lint.py --diff $(REV)

test-quick:
	$(PY) -m pytest tests/ -q -m quick

test:
	$(PY) -m pytest tests/ -q -m 'not slow'

postmortem:
	$(PY) tools/postmortem.py $(DIR)
