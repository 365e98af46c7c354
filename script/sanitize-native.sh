#!/bin/sh
# Sanitizer job for the native C++ hot paths, the rebuild's answer to
# SURVEY §5's race-detection/sanitizer gap.
#
#   ./script/sanitize-native.sh          ASan + UBSan: build an
#       instrumented libgarage_native and run the full oracle cross-check
#       suite against it.  Any overflow, OOB access, or UB in gf8.cpp /
#       blake3.cpp / kvlog.cpp fails the run.
#
#   ./script/sanitize-native.sh --tsan   ThreadSanitizer: rebuild with
#       -fsanitize=thread and hammer the kvlog group-commit machinery —
#       the flusher thread racing committers, barriers and compactions is
#       the only cross-thread surface in the native code (everything else
#       is called from the single asyncio thread).  Data races on the
#       fd/seq counters fail the run.
#
#   ./script/sanitize-native.sh --asan   AddressSanitizer ONLY, kvlog
#       smoke: build the native module with -fsanitize=address and run
#       the group-commit protocol once (commits racing the flusher
#       thread, a barrier, a compaction, reopen).  Fast enough for the
#       slow-marked test in tests/test_db.py.
#
#   ./script/sanitize-native.sh --ubsan  Same smoke under
#       -fsanitize=undefined only (signed overflow, misaligned loads in
#       the frame parser).
#
#   ./script/sanitize-native.sh --all    tsan + asan + ubsan in sequence
#       (each in a fresh child so the LD_PRELOAD runtimes never mix),
#       then one summary table.  Exit 1 if any mode failed.
set -e
cd "$(dirname "$0")/.."

if [ "$1" = "--all" ]; then
    self="$0"
    overall=0
    results=""
    for mode in tsan asan ubsan; do
        start=$(date +%s)
        if "$self" "--$mode" >/tmp/sanitize_${mode}.log 2>&1; then
            status=PASS
        else
            status=FAIL
            overall=1
        fi
        secs=$(( $(date +%s) - start ))
        results="${results}${mode}\t${status}\t${secs}s\t/tmp/sanitize_${mode}.log\n"
    done
    printf '\n=== sanitize-native summary ===\n'
    printf 'MODE\tRESULT\tTIME\tLOG\n'
    printf "%b" "$results"
    [ "$overall" -ne 0 ] && printf 'one or more sanitizer modes FAILED — see logs above\n'
    exit $overall
fi

# --asan / --ubsan: single-sanitizer builds + the kvlog group-commit
# smoke (mirrors --tsan's shape: one mode flag, one focused workload)
if [ "$1" = "--asan" ] || [ "$1" = "--ubsan" ]; then
    if [ "$1" = "--asan" ]; then
        MODE=asan
        SAN_FLAGS="-fsanitize=address"
        RUNTIME=$(g++ -print-file-name=libasan.so)
        export ASAN_OPTIONS=detect_leaks=0
    else
        MODE=ubsan
        SAN_FLAGS="-fsanitize=undefined"
        RUNTIME=$(g++ -print-file-name=libubsan.so)
        export UBSAN_OPTIONS="halt_on_error=1 print_stacktrace=1"
    fi
    SMOKE_SO=/tmp/libgarage_native_${MODE}.so
    g++ -g -O1 -pthread $SAN_FLAGS -fno-sanitize-recover=all \
        -fno-omit-frame-pointer -shared -fPIC -std=c++17 -o "$SMOKE_SO" \
        garage_tpu/_native/gf8.cpp garage_tpu/_native/blake3.cpp \
        garage_tpu/_native/kvlog.cpp

    export GARAGE_NATIVE_SO="$SMOKE_SO"
    export LD_PRELOAD="$RUNTIME"
    export JAX_PLATFORMS=cpu

    python - <<EOF
import os, tempfile

from garage_tpu import _native
from garage_tpu.db.native_engine import NativeDb, _CtypesBinding

assert _native.available(), "$MODE library failed to load"
binding = _CtypesBinding(_native.lib())
tmp = tempfile.mkdtemp()

# group-commit protocol, ONCE: the flusher thread syncs while this
# thread commits, one explicit barrier, one forced compaction, reopen
path = os.path.join(tmp, "smoke-group.log")
db = NativeDb(path, fsync="group", binding=binding)
t = db.open_tree("g")
for i in range(2000):
    t.insert(b"gk%04d" % (i % 256), os.urandom(64))
db.sync_barrier()
db.kv.compact(db.h)
assert db.kv.sync_failures(db.h) == 0
assert len(t) == 256
db.close()
db2 = NativeDb(path, fsync="group", binding=binding)
assert len(db2.open_tree("g")) == 256
db2.close()
print("$MODE: kvlog group-commit smoke clean")
EOF
    exit 0
fi

if [ "$1" = "--tsan" ]; then
    TSAN_SO=/tmp/libgarage_native_tsan.so
    g++ -g -O1 -pthread -fsanitize=thread -fno-omit-frame-pointer \
        -shared -fPIC -std=c++17 -o "$TSAN_SO" \
        garage_tpu/_native/gf8.cpp garage_tpu/_native/blake3.cpp \
        garage_tpu/_native/kvlog.cpp

    LIBTSAN=$(g++ -print-file-name=libtsan.so)
    export GARAGE_NATIVE_SO="$TSAN_SO"
    export LD_PRELOAD="$LIBTSAN"
    # the interpreter is not TSan-built: only our instrumented .so (plus
    # intercepted pthread/malloc) is tracked, which is exactly the
    # flusher-vs-committer surface this mode exists to check
    export TSAN_OPTIONS="halt_on_error=1 exitcode=66 report_thread_leaks=0"
    export JAX_PLATFORMS=cpu

    python - <<'EOF'
import os, tempfile

from garage_tpu import _native
from garage_tpu.db.native_engine import NativeDb, _CtypesBinding

assert _native.available(), "tsan library failed to load"
binding = _CtypesBinding(_native.lib())
tmp = tempfile.mkdtemp()

# group-commit mode: the dedicated flusher thread syncs continuously
# while this thread commits, forces compactions (fd swaps under mu), and
# waits barriers — the full cross-thread protocol, under TSan
path = os.path.join(tmp, "tsan-group.log")
db = NativeDb(path, fsync="group", binding=binding)
t = db.open_tree("g")
for i in range(20000):
    t.insert(b"gk%05d" % (i % 1024), os.urandom(64))
    if i % 500 == 499:
        db.sync_barrier()
    if i % 2000 == 1999:
        db.kv.compact(db.h)
db.sync_barrier()
assert db.kv.sync_failures(db.h) == 0
assert len(t) == 1024
db.close()
db2 = NativeDb(path, fsync="group", binding=binding)
assert len(db2.open_tree("g")) == 1024
db2.close()
print("tsan: group-commit flusher/committer stress clean (no data races)")
EOF
    exit 0
fi

SAN_SO=/tmp/libgarage_native_san.so
# -march=native so the SIMD (pshufb) paths are the ones instrumented
g++ -g -O1 -march=native -pthread -fsanitize=address,undefined \
    -fno-sanitize-recover=all -fno-omit-frame-pointer -shared -fPIC \
    -std=c++17 -o "$SAN_SO" \
    garage_tpu/_native/gf8.cpp garage_tpu/_native/blake3.cpp \
    garage_tpu/_native/kvlog.cpp

LIBASAN=$(g++ -print-file-name=libasan.so)
export GARAGE_NATIVE_SO="$SAN_SO"
export LD_PRELOAD="$LIBASAN"
# the interpreter itself isn't ASan-built: leak checking would drown in
# Python-internal noise; we want memory-error detection in OUR code
export ASAN_OPTIONS=detect_leaks=0
export JAX_PLATFORMS=cpu

python - <<'EOF'
import numpy as np

from garage_tpu import _native
from garage_tpu.ops import gf
from garage_tpu.ops.blake3_ref import blake3 as py_blake3

assert _native.available(), "sanitized library failed to load"
rng = np.random.default_rng(0)

# GF(2^8) codec: many shapes incl. edge sizes, vs the numpy oracle
for r, q, s in [(1, 1, 1), (3, 8, 7), (4, 16, 4096), (3, 8, 65536), (8, 8, 1)]:
    mat = rng.integers(0, 256, (r, q), dtype=np.uint8)
    shards = rng.integers(0, 256, (q, s), dtype=np.uint8)
    got = _native.gf8_apply(mat, shards)
    assert np.array_equal(got, gf.apply_matrix_ref(mat, shards)), (r, q, s)

# BLAKE3: every chunk/block boundary, vs the pure-Python oracle
for n in [0, 1, 63, 64, 65, 1023, 1024, 1025, 2048, 4096, 16384, 100000]:
    d = bytes(rng.integers(0, 256, n, dtype=np.uint8))
    assert _native.blake3(d) == py_blake3(d), n

batch = rng.integers(0, 256, (17, 3072), dtype=np.uint8)
got = _native.blake3_batch(batch)
for i in range(17):
    assert bytes(got[i]) == py_blake3(bytes(batch[i])), i

# kvlog engine (ctypes binding drives the SAME sanitized .so): randomized
# op sequence cross-checked against a plain dict model, plus reopen +
# torn-tail recovery and a corrupt-frame replay — the parser paths where
# OOB reads would hide
import os, random, tempfile
from garage_tpu.db.native_engine import NativeDb, _CtypesBinding

tmp = tempfile.mkdtemp()
path = os.path.join(tmp, "san.log")
binding = _CtypesBinding(_native.lib())
db = NativeDb(path, fsync=False, binding=binding)
t = db.open_tree("t")
model = {}
r = random.Random(7)
for i in range(4000):
    k = bytes([r.randrange(64)]) * r.randrange(1, 40)
    if r.random() < 0.7:
        v = os.urandom(r.randrange(0, 300))
        t.insert(k, v); model[k] = v
    else:
        t.remove(k); model.pop(k, None)
assert dict(t.iter_range()) == model
assert len(t) == len(model)
db.kv.compact(db.h)
assert dict(t.iter_range()) == model
db.close()
# torn tail + trailing garbage must not crash the sanitized replayer
with open(path, "ab") as f:
    f.write(os.urandom(37))
db2 = NativeDb(path, fsync=False, binding=binding)
assert dict(db2.open_tree("t").iter_range()) == model
db2.close()

# group-commit mode: the flusher THREAD races commits/compactions under
# the sanitizer — commit storms, explicit barriers, forced compactions
path3 = os.path.join(tmp, "san-group.log")
db3 = NativeDb(path3, fsync="group", binding=binding)
t3 = db3.open_tree("g")
for i in range(6000):
    t3.insert(b"gk%05d" % (i % 512), os.urandom(64))
    if i % 1000 == 999:
        db3.sync_barrier()
        db3.kv.compact(db3.h)
db3.sync_barrier()
assert len(t3) == 512
db3.close()
db4 = NativeDb(path3, fsync="group", binding=binding)
assert len(db4.open_tree("g")) == 512
db4.close()

print("sanitized native library: all oracle checks passed (ASan+UBSan clean)")
EOF
