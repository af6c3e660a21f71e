# Developer entry points. The Python package needs no build; `native/` holds
# the C++ control/data-plane daemons.

.PHONY: test test-all lint check lockcheck racecheck jitcheck native tsan bench lm-bench data-bench gen-bench dryrun clean

test:  ## fast tier (<2 min on CPU); compile-heavy tests are marked slow
	python -m pytest tests/ -q -m "not slow"

lint:  ## ruff (when installed) + bytecode-compile + project-aware `slt check`
	@if command -v ruff >/dev/null 2>&1; then \
		ruff check serverless_learn_tpu tests benchmarks; \
	else \
		echo "ruff not installed; skipping style pass"; \
	fi
	python -m compileall -q serverless_learn_tpu tests benchmarks bench.py
	python -m serverless_learn_tpu check

check:  ## project-aware static analysis alone (SLT001-SLT013)
	python -m serverless_learn_tpu check

lockcheck:  ## fast telemetry/health/goodput tier under the runtime lock-order detector
	SLT_LOCKCHECK=1 python -m pytest tests/test_analysis.py tests/test_telemetry.py \
		tests/test_health.py tests/test_goodput.py tests/test_canary.py \
		tests/test_regress.py -q -m "not slow"

racecheck:  ## concurrency surface under the vector-clock happens-before race detector
	SLT_RACECHECK=1 python -m pytest tests/test_fleet.py tests/test_gossip.py \
		tests/test_kvcache.py tests/test_continuous.py tests/test_telemetry.py \
		tests/test_health.py tests/test_canary.py tests/test_regress.py \
		-q -m "not slow"

jitcheck:  ## inference/training compile discipline under the runtime jit monitor
	SLT_JITCHECK=1 python -m pytest tests/test_continuous.py \
		tests/test_kvcache.py tests/test_train_step.py \
		tests/test_grad_accum_eval.py tests/test_jitcheck.py \
		-q -m "not slow"
	python -m serverless_learn_tpu jit --self-check

test-all:  ## the full suite (~13 min on CPU)
	python -m pytest tests/ -q

native:
	$(MAKE) -C native

tsan:
	$(MAKE) -C native tsan

bench:  ## headline benchmark (real TPU chip)
	python bench.py

lm-bench:
	python benchmarks/lm_bench.py --compare-fused

data-bench:
	python benchmarks/data_bench.py

gen-bench:
	python benchmarks/gen_bench.py

dryrun:  ## multichip sharding compile check on 8 virtual CPU devices
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		python __graft_entry__.py

clean:
	$(MAKE) -C native clean
	find . -name __pycache__ -type d -exec rm -rf {} +
