# One-command CI gate (reference scripts/travis/travis_script.sh:19-67:
# lint + gtest + sanitizer + endian runs per commit, rebuilt here as a
# single `make check`). Every step exits nonzero on failure.
#
#   make check        full gate: syntax lint, optimized native build,
#                     pytest (incl. native-vs-Python differential fuzz,
#                     tests/test_native.py::test_fuzz_parity), ASan +
#                     TSan rebuilds with the native parity suites under
#                     the sanitizer runtime, optimized rebuild, and the
#                     single-chip + 8-device-mesh dryrun
#   make test         pytest only
#   make native       optimized native core only
#   make bench        the benchmark's main cell, criteo_fm.seq (TPU only)

PY ?= python
LIBASAN := $(shell gcc -print-file-name=libasan.so)
LIBTSAN := $(shell gcc -print-file-name=libtsan.so)
# the suites that exercise the native .so (what the sanitizers can see)
NATIVE_TESTS := tests/test_native.py tests/test_fused.py tests/test_rowrec.py tests/test_libfm_ell.py tests/test_libsvm_ell.py

.PHONY: check lint native test sanitizers dryrun bench clean

check: lint native test sanitizers dryrun
	@echo "== make check: ALL GATES PASSED =="

lint:
	$(PY) -m compileall -q dmlc_core_tpu tests benchmarks __graft_entry__.py
	$(PY) tools/lint.py

native:
	$(MAKE) -C native

test: native
	$(PY) -m pytest tests/ -q

sanitizers:
	$(MAKE) -C native asan
	LD_PRELOAD=$(LIBASAN) ASAN_OPTIONS=detect_leaks=0 \
		$(PY) -m pytest $(NATIVE_TESTS) -q -p no:cacheprovider -m "not jax"
	$(MAKE) -C native tsan
	LD_PRELOAD=$(LIBTSAN) TSAN_OPTIONS=report_bugs=1 \
		$(PY) -m pytest tests/test_native.py -q -p no:cacheprovider
	$(MAKE) -C native   # leave the optimized build behind, never a sanitizer one

dryrun: native
	$(PY) __graft_entry__.py

bench: native
	$(PY) benchmarks/chip/run.py --workload criteo_fm.seq --seed 1 --seconds 20 --trace 0

clean:
	$(MAKE) -C native clean
