# Single CI entry: tier-1 tests + the batched-data-plane and serverless
# bench smokes. Everything runs on any host (simulated fabric; Pallas
# kernels in interpret mode on a CPU); no TPU required. On a TPU host,
# `python3 chip_smoke.py` drives the compiled device paths.

PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))
export PYTHONPATH

.PHONY: verify test smoke bench apicheck

verify: test smoke apicheck

test:
	python -m pytest -x -q

smoke:
	python -m benchmarks.run --smoke

# deprecation surface: clients are Session-only outside core/, and the
# legacy sys_q* shim module warns exactly once on import
apicheck:
	python tools/check_api_surface.py

bench:
	python -m benchmarks.batched_lookup
	python -m benchmarks.serverless
	python -m benchmarks.run

