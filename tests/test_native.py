"""Native-core tests: C++ io pipeline + C predict ABI.

Reference models: src/io/iter_image_recordio_2.cc coverage in
tests/python/unittest/test_io.py, and src/c_api/c_predict_api.cc's
predict contract (SURVEY.md §2.1 L9, §3.5).
"""
import ctypes
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import native
from mxnet_tpu.gluon import nn
from mxnet_tpu.io import ImageRecordIter
from mxnet_tpu.recordio import IRHeader, MXRecordIO, pack_img

pytestmark = pytest.mark.skipif(
    shutil.which("g++") is None, reason="no C++ toolchain")


def _make_rec(path, n=48, h=240, w=260, label_width=1, seed=0):
    rng = np.random.default_rng(seed)
    rec = MXRecordIO(path, "w")
    for i in range(n):
        img = rng.integers(0, 255, (h, w, 3), dtype=np.uint8)
        if label_width == 1:
            hdr = IRHeader(0, float(i % 10), i, 0)
        else:
            hdr = IRHeader(0, np.arange(label_width, dtype=np.float32) + i,
                           i, 0)
        rec.write(pack_img(hdr, img, quality=90))
    rec.close()


def test_native_matches_python_path(tmp_path):
    path = str(tmp_path / "a.rec")
    _make_rec(path)
    kw = dict(data_shape=(3, 224, 224), batch_size=16,
              preprocess_threads=4)
    bn = next(iter(ImageRecordIter(path, use_native=True,
                                   scaled_decode=False, **kw)))
    bp = next(iter(ImageRecordIter(path, use_native=False, **kw)))
    # same libjpeg underneath → identical decode, identical center crop
    np.testing.assert_array_equal(bn.label[0].asnumpy(),
                                  bp.label[0].asnumpy())
    np.testing.assert_allclose(bn.data[0].asnumpy(),
                               bp.data[0].asnumpy(), atol=1.0)


def test_native_epochs_shuffle_and_augment(tmp_path):
    path = str(tmp_path / "b.rec")
    _make_rec(path, n=32)
    it = ImageRecordIter(path, (3, 128, 128), 8, use_native=True,
                         shuffle=True, rand_crop=True, rand_mirror=True,
                         resize=160, mean_r=123.0, mean_g=117.0,
                         mean_b=104.0, std_r=58.0, std_g=57.0, std_b=57.0,
                         seed=7)
    e1 = [b.label[0].asnumpy().copy() for b in it]
    it.reset()
    e2 = [b.label[0].asnumpy().copy() for b in it]
    assert len(e1) == len(e2) == 4
    flat1 = np.concatenate(e1)
    flat2 = np.concatenate(e2)
    # every sample seen exactly once per epoch, different order per epoch
    assert sorted(flat1 % 10) == sorted(flat2 % 10)
    assert not np.array_equal(flat1, flat2)


def test_native_round_batch_pad(tmp_path):
    path = str(tmp_path / "c.rec")
    _make_rec(path, n=20)
    it = ImageRecordIter(path, (3, 96, 96), 8, use_native=True)
    pads = [b.pad for b in it]
    assert pads == [0, 0, 4]          # 20 = 8+8+4 → last batch wraps 4


def test_native_part_index_sharding(tmp_path):
    path = str(tmp_path / "d.rec")
    _make_rec(path, n=40)
    seen = []
    for part in range(2):
        it = ImageRecordIter(path, (3, 64, 64), 10, use_native=True,
                             part_index=part, num_parts=2)
        for b in it:
            seen.append(b.label[0].asnumpy())
    labels = np.concatenate(seen)
    assert len(labels) == 40          # both shards together cover all


def test_native_multi_label(tmp_path):
    path = str(tmp_path / "e.rec")
    _make_rec(path, n=12, label_width=3)
    it = ImageRecordIter(path, (3, 64, 64), 4, use_native=True,
                         label_width=3)
    b = next(iter(it))
    lab = b.label[0].asnumpy()
    assert lab.shape == (4, 3)
    np.testing.assert_allclose(lab[0], [0, 1, 2])


# ---------------------------------------------------------------------------
# C predict ABI
# ---------------------------------------------------------------------------

def _export_small_net(prefix):
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Conv2D(4, 3, padding=1, activation="relu"))
        net.add(nn.Flatten())
        net.add(nn.Dense(5))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    x = np.random.randn(2, 3, 8, 8).astype(np.float32)
    ref = net(mx.nd.array(x)).asnumpy()
    net.export(prefix)
    return x, ref


def test_predict_abi_in_process(tmp_path):
    prefix = str(tmp_path / "m")
    x, ref = _export_small_net(prefix)
    lib = native.load_predict()
    sym_json = open(f"{prefix}-symbol.json").read().encode()
    params = open(f"{prefix}-0000.params", "rb").read()
    keys = (ctypes.c_char_p * 1)(b"data")
    indptr = (ctypes.c_uint32 * 2)(0, 4)
    shape = (ctypes.c_uint32 * 4)(2, 3, 8, 8)
    h = ctypes.c_void_p()
    rc = lib.MXPredCreate(sym_json, params, len(params), 1, 0, 1,
                          keys, indptr, shape, ctypes.byref(h))
    assert rc == 0, lib.MXGetLastError().decode()
    xf = np.ascontiguousarray(x)
    fp = ctypes.POINTER(ctypes.c_float)
    assert lib.MXPredSetInput(h, b"data", xf.ctypes.data_as(fp),
                              xf.size) == 0
    assert lib.MXPredForward(h) == 0
    sd = ctypes.POINTER(ctypes.c_uint32)()
    ndim = ctypes.c_uint32()
    assert lib.MXPredGetOutputShape(h, 0, ctypes.byref(sd),
                                    ctypes.byref(ndim)) == 0
    oshape = [sd[i] for i in range(ndim.value)]
    out = np.empty(oshape, np.float32)
    assert lib.MXPredGetOutput(h, 0, out.ctypes.data_as(fp),
                               out.size) == 0
    lib.MXPredFree(h)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


def test_predict_abi_reports_errors(tmp_path):
    prefix = str(tmp_path / "m2")
    _export_small_net(prefix)
    lib = native.load_predict()
    h = ctypes.c_void_p()
    keys = (ctypes.c_char_p * 1)(b"data")
    indptr = (ctypes.c_uint32 * 2)(0, 1)
    shape = (ctypes.c_uint32 * 1)(3)
    rc = lib.MXPredCreate(b"{not json", b"", 0, 1, 0, 1, keys, indptr,
                          shape, ctypes.byref(h))
    assert rc != 0
    assert len(lib.MXGetLastError()) > 0


C_HOST = r"""
#include <dlfcn.h>
#include <stdint.h>
#include <stdio.h>
#include <stdlib.h>
typedef int (*create_fn)(const char*, const void*, int, int, int,
                         uint32_t, const char**, const uint32_t*,
                         const uint32_t*, void**);
typedef int (*setin_fn)(void*, const char*, const float*, uint32_t);
typedef int (*fwd_fn)(void*);
typedef int (*out_fn)(void*, uint32_t, float*, uint32_t);
typedef const char* (*err_fn)(void);
static char* slurp(const char* p, long* n) {
  FILE* f = fopen(p, "rb"); fseek(f, 0, SEEK_END); *n = ftell(f);
  fseek(f, 0, SEEK_SET); char* b = malloc(*n + 1);
  fread(b, 1, *n, f); b[*n] = 0; fclose(f); return b;
}
int main(int argc, char** argv) {
  void* so = dlopen(argv[1], RTLD_NOW | RTLD_GLOBAL);
  if (!so) { fprintf(stderr, "%s\n", dlerror()); return 2; }
  create_fn create = (create_fn)dlsym(so, "MXPredCreate");
  setin_fn setin = (setin_fn)dlsym(so, "MXPredSetInput");
  fwd_fn fwd = (fwd_fn)dlsym(so, "MXPredForward");
  out_fn getout = (out_fn)dlsym(so, "MXPredGetOutput");
  err_fn lasterr = (err_fn)dlsym(so, "MXGetLastError");
  long jn, pn;
  char* json = slurp(argv[2], &jn);
  char* params = slurp(argv[3], &pn);
  const char* keys[1] = {"data"};
  uint32_t indptr[2] = {0, 4};
  uint32_t shape[4] = {2, 3, 8, 8};
  void* h = NULL;
  if (create(json, params, (int)pn, 1, 0, 1, keys, indptr, shape, &h)) {
    fprintf(stderr, "create: %s\n", lasterr()); return 1; }
  float x[2 * 3 * 8 * 8];
  for (int i = 0; i < 2 * 3 * 8 * 8; i++) x[i] = (float)(i % 7) * 0.1f;
  if (setin(h, "data", x, 2 * 3 * 8 * 8)) return 1;
  if (fwd(h)) { fprintf(stderr, "fwd: %s\n", lasterr()); return 1; }
  float out[10];
  if (getout(h, 0, out, 10)) return 1;
  printf("C-HOST-OK\n");
  return 0;
}
"""


def test_predict_abi_from_pure_c_host(tmp_path):
    """A C binary with no Python linkage dlopens the .so and predicts —
    the reference's embedding story (amalgamation/c_predict_api users)."""
    if shutil.which("gcc") is None:
        pytest.skip("no C compiler")
    prefix = str(tmp_path / "m3")
    _export_small_net(prefix)
    native.load_predict()            # ensure the .so is built
    so = os.path.join(os.path.dirname(native.__file__),
                      "libmxtpu_predict.so")
    csrc = tmp_path / "host.c"
    csrc.write_text(C_HOST)
    exe = str(tmp_path / "host")
    subprocess.run(["gcc", "-O2", "-o", exe, str(csrc), "-ldl"],
                   check=True)
    env = dict(os.environ,
               JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [exe, so, f"{prefix}-symbol.json", f"{prefix}-0000.params"],
        capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr
    assert "C-HOST-OK" in r.stdout


# ---------------------------------------------------------------------------
# imperative C ABI (ndarray_core.cc — reference c_api.cc/c_api_ndarray.cc)
# ---------------------------------------------------------------------------

def test_ndarray_abi_in_process():
    """ctypes drive of the MXNDArray*/MXImperativeInvoke slice: create two
    arrays, upload data, invoke `dot` with a transpose attr, read back."""
    import ctypes
    lib = native.load_ndarray()
    u32, vp = ctypes.c_uint32, ctypes.c_void_p

    def make(shape_t, values):
        sh = (u32 * len(shape_t))(*shape_t)
        h = vp()
        assert lib.MXNDArrayCreate(sh, len(shape_t), 1, 0, 0,
                                   ctypes.byref(h)) == 0, \
            lib.MXNDGetLastError()
        arr = np.ascontiguousarray(values, np.float32)
        assert lib.MXNDArraySyncCopyFromCPU(
            h, arr.ctypes.data_as(vp), arr.size) == 0
        return h

    a_np = np.arange(6, dtype=np.float32).reshape(2, 3)
    b_np = np.arange(12, dtype=np.float32).reshape(4, 3) * 0.5
    ha, hb = make((2, 3), a_np), make((4, 3), b_np)

    # shape/dtype introspection
    ndim = u32()
    pdata = ctypes.POINTER(u32)()
    assert lib.MXNDArrayGetShape(ha, ctypes.byref(ndim),
                                 ctypes.byref(pdata)) == 0
    assert [pdata[i] for i in range(ndim.value)] == [2, 3]
    dt = ctypes.c_int()
    assert lib.MXNDArrayGetDType(ha, ctypes.byref(dt)) == 0
    assert dt.value == 0                      # float32

    # registry surfaces through C
    n_ops = u32()
    names = ctypes.POINTER(ctypes.c_char_p)()
    assert lib.MXListAllOpNames(ctypes.byref(n_ops),
                                ctypes.byref(names)) == 0
    assert n_ops.value >= 300
    op = vp()
    assert lib.NNGetOpHandle(b"dot", ctypes.byref(op)) == 0

    # invoke dot(a, b, transpose_b=True) -> (2, 4)
    ins = (vp * 2)(ha, hb)
    n_out = ctypes.c_int()
    outs = ctypes.POINTER(vp)()
    keys = (ctypes.c_char_p * 1)(b"transpose_b")
    vals = (ctypes.c_char_p * 1)(b"True")
    assert lib.MXImperativeInvoke(op, 2, ins, ctypes.byref(n_out),
                                  ctypes.byref(outs), 1, keys, vals) == 0, \
        lib.MXNDGetLastError()
    assert n_out.value == 1
    out_h = outs[0]
    assert lib.MXNDArrayGetShape(out_h, ctypes.byref(ndim),
                                 ctypes.byref(pdata)) == 0
    out_shape = tuple(pdata[i] for i in range(ndim.value))
    assert out_shape == (2, 4)
    buf = np.empty(out_shape, np.float32)
    assert lib.MXNDArraySyncCopyToCPU(out_h, buf.ctypes.data_as(vp),
                                      buf.size) == 0
    np.testing.assert_allclose(buf, a_np @ b_np.T, rtol=1e-6)
    assert lib.MXNDArrayWaitAll() == 0

    # unknown op reports through MXNDGetLastError
    bad = vp()
    assert lib.NNGetOpHandle(b"definitely_not_an_op",
                             ctypes.byref(bad)) != 0
    assert b"not registered" in lib.MXNDGetLastError()
    for h in (ha, hb, out_h):
        lib.MXNDArrayFree(h)


ND_C_HOST = r"""
#include <dlfcn.h>
#include <stdint.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
typedef int (*create_fn)(const uint32_t*, uint32_t, int, int, int, void**);
typedef int (*copyfrom_fn)(void*, const void*, size_t);
typedef int (*copyto_fn)(void*, void*, size_t);
typedef int (*getshape_fn)(void*, uint32_t*, const uint32_t**);
typedef int (*ophandle_fn)(const char*, void**);
typedef int (*invoke_fn)(void*, int, void**, int*, void***, int,
                         const char**, const char**);
typedef int (*free_fn)(void*);
typedef const char* (*err_fn)(void);
int main(int argc, char** argv) {
  void* so = dlopen(argv[1], RTLD_NOW | RTLD_GLOBAL);
  if (!so) { fprintf(stderr, "%s\n", dlerror()); return 2; }
  create_fn nd_create = (create_fn)dlsym(so, "MXNDArrayCreate");
  copyfrom_fn nd_from = (copyfrom_fn)dlsym(so, "MXNDArraySyncCopyFromCPU");
  copyto_fn nd_to = (copyto_fn)dlsym(so, "MXNDArraySyncCopyToCPU");
  getshape_fn nd_shape = (getshape_fn)dlsym(so, "MXNDArrayGetShape");
  ophandle_fn op_get = (ophandle_fn)dlsym(so, "NNGetOpHandle");
  invoke_fn invoke = (invoke_fn)dlsym(so, "MXImperativeInvoke");
  free_fn nd_free = (free_fn)dlsym(so, "MXNDArrayFree");
  err_fn lasterr = (err_fn)dlsym(so, "MXNDGetLastError");

  uint32_t sa[2] = {2, 3}, sb[2] = {3, 2};
  void *ha = NULL, *hb = NULL;
  if (nd_create(sa, 2, 1, 0, 0, &ha)) {
    fprintf(stderr, "create: %s\n", lasterr()); return 1; }
  if (nd_create(sb, 2, 1, 0, 0, &hb)) return 1;
  float a[6] = {1, 2, 3, 4, 5, 6}, b[6] = {1, 0, 0, 1, 1, 1};
  if (nd_from(ha, a, 6) || nd_from(hb, b, 6)) return 1;

  void* op = NULL;
  if (op_get("dot", &op)) { fprintf(stderr, "op: %s\n", lasterr()); return 1; }
  void* ins[2]; ins[0] = ha; ins[1] = hb;
  int n_out = 0; void** outs = NULL;
  if (invoke(op, 2, ins, &n_out, &outs, 0, NULL, NULL)) {
    fprintf(stderr, "invoke: %s\n", lasterr()); return 1; }
  uint32_t ndim = 0; const uint32_t* shp = NULL;
  if (nd_shape(outs[0], &ndim, &shp) || ndim != 2 || shp[0] != 2
      || shp[1] != 2) { fprintf(stderr, "shape wrong\n"); return 1; }
  float out[4];
  if (nd_to(outs[0], out, 4)) return 1;
  /* [[1,2,3],[4,5,6]] @ [[1,0],[0,1],[1,1]] = [[4,5],[10,11]] */
  if (out[0] != 4 || out[1] != 5 || out[2] != 10 || out[3] != 11) {
    fprintf(stderr, "values wrong: %f %f %f %f\n",
            out[0], out[1], out[2], out[3]);
    return 1;
  }
  nd_free(ha); nd_free(hb); nd_free(outs[0]);
  printf("ND-C-HOST-OK\n");
  return 0;
}
"""


def test_ndarray_abi_from_pure_c_host(tmp_path):
    """A C binary with no Python linkage creates arrays, invokes `dot`
    through the registry, and reads the result back — the reference's
    language-binding story (c_api.cc is what Scala/Julia/R bind against)."""
    if shutil.which("gcc") is None:
        pytest.skip("no C compiler")
    native.load_ndarray()            # ensure the .so is built
    so = os.path.join(os.path.dirname(native.__file__),
                      "libmxtpu_ndarray.so")
    csrc = tmp_path / "nd_host.c"
    csrc.write_text(ND_C_HOST)
    exe = str(tmp_path / "nd_host")
    subprocess.run(["gcc", "-O2", "-o", exe, str(csrc), "-ldl"],
                   check=True)
    env = dict(os.environ,
               JAX_PLATFORMS="cpu")
    r = subprocess.run([exe, so], capture_output=True, text=True,
                       timeout=300, env=env)
    assert r.returncode == 0, r.stderr
    assert "ND-C-HOST-OK" in r.stdout


def test_ndarray_abi_inplace_out_and_bounds():
    """Reference c_api_ndarray.cc contracts: caller-supplied output handles
    mean in-place write; SyncCopyToCPU must refuse a too-small buffer."""
    import ctypes
    lib = native.load_ndarray()
    u32, vp = ctypes.c_uint32, ctypes.c_void_p

    def make(shape_t, values):
        sh = (u32 * len(shape_t))(*shape_t)
        h = vp()
        assert lib.MXNDArrayCreate(sh, len(shape_t), 1, 0, 0,
                                   ctypes.byref(h)) == 0
        arr = np.ascontiguousarray(values, np.float32)
        assert lib.MXNDArraySyncCopyFromCPU(
            h, arr.ctypes.data_as(vp), arr.size) == 0
        return h

    a = make((2, 2), np.ones((2, 2)))
    b = make((2, 2), 2 * np.ones((2, 2)))
    dst = make((2, 2), np.zeros((2, 2)))
    op = vp()
    assert lib.NNGetOpHandle(b"broadcast_add", ctypes.byref(op)) == 0
    ins = (vp * 2)(a, b)
    outs_arr = (vp * 1)(dst)
    outs = ctypes.cast(outs_arr, ctypes.POINTER(vp))
    n_out = ctypes.c_int(1)
    assert lib.MXImperativeInvoke(op, 2, ins, ctypes.byref(n_out),
                                  ctypes.byref(outs), 0, None, None) == 0, \
        lib.MXNDGetLastError()
    buf = np.empty((2, 2), np.float32)
    assert lib.MXNDArraySyncCopyToCPU(dst, buf.ctypes.data_as(vp),
                                      buf.size) == 0
    np.testing.assert_allclose(buf, 3.0)      # written IN PLACE into dst

    # bounds: reading a 4-element array into a 2-element buffer must fail
    small = np.empty(2, np.float32)
    assert lib.MXNDArraySyncCopyToCPU(dst, small.ctypes.data_as(vp),
                                      small.size) != 0
    assert b"too small" in lib.MXNDGetLastError()
    for h in (a, b, dst):
        lib.MXNDArrayFree(h)


# ---------------------------------------------------------------------------
# symbol C ABI (symbol_core.cc — reference src/c_api/c_api_symbolic.cc):
# graph CONSTRUCTION from C, the surface the reference's language bindings
# build models through (atomic-symbol + compose loops)
# ---------------------------------------------------------------------------

def _sym_check(lib, rc):
    if rc != 0:
        raise AssertionError(lib.MXSymGetLastError().decode())


def test_symbol_abi_compose_json_infer():
    """Variable -> CreateAtomicSymbol(FullyConnected) -> Compose -> lists,
    JSON round-trip, InferShape (CSR in/out) — all through ctypes."""
    lib = native.load_symbol()
    vp = ctypes.c_void_p
    u32 = ctypes.c_uint32

    data = vp()
    _sym_check(lib, lib.MXSymbolCreateVariable(b"data", ctypes.byref(data)))
    keys = (ctypes.c_char_p * 2)(b"num_hidden", b"no_bias")
    vals = (ctypes.c_char_p * 2)(b"8", b"True")
    fc = vp()
    _sym_check(lib, lib.MXSymbolCreateAtomicSymbol(
        b"FullyConnected", 2, keys, vals, ctypes.byref(fc)))
    args = (vp * 1)(data)
    _sym_check(lib, lib.MXSymbolCompose(fc, b"fc1", 1, None, args))

    n = u32()
    arr = ctypes.POINTER(ctypes.c_char_p)()
    _sym_check(lib, lib.MXSymbolListArguments(fc, ctypes.byref(n),
                                              ctypes.byref(arr)))
    names = [arr[i].decode() for i in range(n.value)]
    assert names == ["data", "fc1_weight"]
    _sym_check(lib, lib.MXSymbolListOutputs(fc, ctypes.byref(n),
                                            ctypes.byref(arr)))
    assert [arr[i].decode() for i in range(n.value)] == ["fc1_output"]

    js = ctypes.c_char_p()
    _sym_check(lib, lib.MXSymbolSaveToJSON(fc, ctypes.byref(js)))
    h2 = vp()
    _sym_check(lib, lib.MXSymbolCreateFromJSON(js.value, ctypes.byref(h2)))

    # the reloaded graph must agree with the python frontend's view
    s = mx.sym.load_json(js.value.decode())
    assert s.list_arguments() == ["data", "fc1_weight"]

    keys2 = (ctypes.c_char_p * 1)(b"data")
    ind = (u32 * 2)(0, 2)
    shp = (u32 * 2)(4, 16)
    iss, oss, ass_ = u32(), u32(), u32()
    isn = ctypes.POINTER(u32)()
    osn = ctypes.POINTER(u32)()
    asn = ctypes.POINTER(u32)()
    isd = ctypes.POINTER(ctypes.POINTER(u32))()
    osd = ctypes.POINTER(ctypes.POINTER(u32))()
    asd = ctypes.POINTER(ctypes.POINTER(u32))()
    comp = ctypes.c_int()
    _sym_check(lib, lib.MXSymbolInferShape(
        h2, 1, keys2, ind, shp,
        ctypes.byref(iss), ctypes.byref(isn), ctypes.byref(isd),
        ctypes.byref(oss), ctypes.byref(osn), ctypes.byref(osd),
        ctypes.byref(ass_), ctypes.byref(asn), ctypes.byref(asd),
        ctypes.byref(comp)))
    assert comp.value == 1
    in_shapes = [[isd[i][d] for d in range(isn[i])]
                 for i in range(iss.value)]
    out_shapes = [[osd[i][d] for d in range(osn[i])]
                  for i in range(oss.value)]
    assert out_shapes == [[4, 8]]
    assert in_shapes == [[4, 16], [8, 16]]     # data, fc1_weight (O, I)

    # named-argument compose (keys non-NULL) binds by input name
    d2 = vp()
    _sym_check(lib, lib.MXSymbolCreateVariable(b"x", ctypes.byref(d2)))
    act = vp()
    akeys = (ctypes.c_char_p * 1)(b"act_type")
    avals = (ctypes.c_char_p * 1)(b"relu")
    _sym_check(lib, lib.MXSymbolCreateAtomicSymbol(
        b"Activation", 1, akeys, avals, ctypes.byref(act)))
    ckeys = (ctypes.c_char_p * 1)(b"data")
    cargs = (vp * 1)(d2)
    _sym_check(lib, lib.MXSymbolCompose(act, b"relu0", 1, ckeys, cargs))
    _sym_check(lib, lib.MXSymbolListArguments(act, ctypes.byref(n),
                                              ctypes.byref(arr)))
    assert [arr[i].decode() for i in range(n.value)] == ["x"]

    # error surface: bad JSON must fail with a message
    bad = vp()
    assert lib.MXSymbolCreateFromJSON(b"not json",
                                      ctypes.byref(bad)) != 0
    assert len(lib.MXSymGetLastError()) > 0
    for h in (data, fc, h2, d2, act):
        lib.MXSymbolFree(h)


SYM_C_HOST = r"""
#include <dlfcn.h>
#include <stdint.h>
#include <stdio.h>
#include <string.h>
typedef int (*var_fn)(const char*, void**);
typedef int (*atomic_fn)(const char*, uint32_t, const char**, const char**,
                         void**);
typedef int (*compose_fn)(void*, const char*, uint32_t, const char**,
                          void**);
typedef int (*list_fn)(void*, uint32_t*, const char***);
typedef int (*tojson_fn)(void*, const char**);
typedef int (*fromjson_fn)(const char*, void**);
typedef int (*infer_fn)(void*, uint32_t, const char**, const uint32_t*,
                        const uint32_t*, uint32_t*, const uint32_t**,
                        const uint32_t***, uint32_t*, const uint32_t**,
                        const uint32_t***, uint32_t*, const uint32_t**,
                        const uint32_t***, int*);
typedef int (*free_fn)(void*);
typedef const char* (*err_fn)(void);
int main(int argc, char** argv) {
  void* so = dlopen(argv[1], RTLD_NOW | RTLD_GLOBAL);
  if (!so) { fprintf(stderr, "%s\n", dlerror()); return 2; }
  var_fn mkvar = (var_fn)dlsym(so, "MXSymbolCreateVariable");
  atomic_fn atomic = (atomic_fn)dlsym(so, "MXSymbolCreateAtomicSymbol");
  compose_fn compose = (compose_fn)dlsym(so, "MXSymbolCompose");
  list_fn listargs = (list_fn)dlsym(so, "MXSymbolListArguments");
  tojson_fn tojson = (tojson_fn)dlsym(so, "MXSymbolSaveToJSON");
  fromjson_fn fromjson = (fromjson_fn)dlsym(so, "MXSymbolCreateFromJSON");
  infer_fn infer = (infer_fn)dlsym(so, "MXSymbolInferShape");
  free_fn sfree = (free_fn)dlsym(so, "MXSymbolFree");
  err_fn lasterr = (err_fn)dlsym(so, "MXSymGetLastError");

  void* x = NULL;
  if (mkvar("x", &x)) { fprintf(stderr, "var: %s\n", lasterr()); return 1; }
  const char* keys[1]; const char* vals[1];
  keys[0] = "num_hidden"; vals[0] = "4";
  void* fc = NULL;
  if (atomic("FullyConnected", 1, keys, vals, &fc)) {
    fprintf(stderr, "atomic: %s\n", lasterr()); return 1; }
  void* args[1]; args[0] = x;
  if (compose(fc, "out", 1, NULL, args)) {
    fprintf(stderr, "compose: %s\n", lasterr()); return 1; }

  uint32_t n = 0; const char** names = NULL;
  if (listargs(fc, &n, &names) || n != 3) {
    fprintf(stderr, "listargs: %s\n", lasterr()); return 1; }
  /* x, out_weight, out_bias */
  if (strcmp(names[0], "x") != 0) return 1;

  const char* js = NULL;
  if (tojson(fc, &js)) return 1;
  void* clone = NULL;
  if (fromjson(js, &clone)) return 1;

  const char* ikeys[1]; ikeys[0] = "x";
  uint32_t ind[2]; ind[0] = 0; ind[1] = 2;
  uint32_t shp[2]; shp[0] = 2; shp[1] = 6;
  uint32_t iss, oss, ass; const uint32_t *isn, *osn, *asn;
  const uint32_t **isd, **osd, **asd; int comp = 0;
  if (infer(clone, 1, ikeys, ind, shp, &iss, &isn, &isd, &oss, &osn, &osd,
            &ass, &asn, &asd, &comp)) {
    fprintf(stderr, "infer: %s\n", lasterr()); return 1; }
  if (oss != 1 || osn[0] != 2 || osd[0][0] != 2 || osd[0][1] != 4) {
    fprintf(stderr, "bad out shape\n"); return 1; }
  sfree(x); sfree(fc); sfree(clone);
  printf("SYM-C-HOST-OK\n");
  return 0;
}
"""


def test_symbol_abi_from_pure_c_host(tmp_path):
    """A C binary with no Python linkage builds an FC graph through
    atomic+compose, JSON round-trips it, and infers shapes — the
    reference's model-constructor story for non-Python bindings."""
    if shutil.which("gcc") is None:
        pytest.skip("no C compiler")
    native.load_symbol()             # ensure the .so is built
    so = os.path.join(os.path.dirname(native.__file__),
                      "libmxtpu_symbol.so")
    csrc = tmp_path / "sym_host.c"
    csrc.write_text(SYM_C_HOST)
    exe = str(tmp_path / "sym_host")
    subprocess.run(["gcc", "-O2", "-o", exe, str(csrc), "-ldl"],
                   check=True)
    env = dict(os.environ,
               JAX_PLATFORMS="cpu")
    r = subprocess.run([exe, so], capture_output=True, text=True,
                       timeout=300, env=env)
    assert r.returncode == 0, r.stderr
    assert "SYM-C-HOST-OK" in r.stdout


def test_symbol_abi_partial_infer_shape():
    """Under-specified inputs are not an error: rc=0 with complete=0
    (reference c_api_symbolic.cc partial-inference contract)."""
    lib = native.load_symbol()
    vp = ctypes.c_void_p
    u32 = ctypes.c_uint32
    # two unknowable inputs: without shapes for both, inference is partial
    js = (mx.sym.Variable("a") + mx.sym.Variable("b")).tojson()
    h = vp()
    _sym_check(lib, lib.MXSymbolCreateFromJSON(js.encode(),
                                               ctypes.byref(h)))
    iss, oss, ass_ = u32(), u32(), u32()
    isn = ctypes.POINTER(u32)()
    osn = ctypes.POINTER(u32)()
    asn = ctypes.POINTER(u32)()
    isd = ctypes.POINTER(ctypes.POINTER(u32))()
    osd = ctypes.POINTER(ctypes.POINTER(u32))()
    asd = ctypes.POINTER(ctypes.POINTER(u32))()
    comp = ctypes.c_int(7)
    _sym_check(lib, lib.MXSymbolInferShape(
        h, 0, None, (u32 * 1)(0), None,
        ctypes.byref(iss), ctypes.byref(isn), ctypes.byref(isd),
        ctypes.byref(oss), ctypes.byref(osn), ctypes.byref(osd),
        ctypes.byref(ass_), ctypes.byref(asn), ctypes.byref(asd),
        ctypes.byref(comp)))
    assert comp.value == 0
    assert iss.value == 0 and oss.value == 0
    lib.MXSymbolFree(h)


def test_kvstore_abi_init_push_pull():
    """MXKVStore* slice (reference c_api.cc): create/init/push/pull with
    int keys; pushed values are MXNDArray* handles from the same .so, a
    repeated key is a multi-device push that reduces before the updater
    (KVStoreLocal semantics)."""
    lib = native.load_ndarray()
    vp = ctypes.c_void_p
    u32 = ctypes.c_uint32

    def check(rc):
        assert rc == 0, lib.MXNDGetLastError().decode()

    def make_nd(arr):
        arr = np.ascontiguousarray(arr, np.float32)
        shp = (u32 * arr.ndim)(*arr.shape)
        h = vp()
        check(lib.MXNDArrayCreate(shp, arr.ndim, 1, 0, 0,
                                  ctypes.byref(h)))
        check(lib.MXNDArraySyncCopyFromCPU(h, arr.ctypes.data_as(vp),
                                           arr.size))
        return h

    kv = vp()
    check(lib.MXKVStoreCreate(b"local", ctypes.byref(kv)))
    t = ctypes.c_char_p()
    check(lib.MXKVStoreGetType(kv, ctypes.byref(t)))
    assert t.value == b"local"
    r, g = ctypes.c_int(), ctypes.c_int()
    check(lib.MXKVStoreGetRank(kv, ctypes.byref(r)))
    check(lib.MXKVStoreGetGroupSize(kv, ctypes.byref(g)))
    assert (r.value, g.value) == (0, 1)

    init = make_nd(np.zeros((2, 2)))
    check(lib.MXKVStoreInit(kv, 1, (ctypes.c_int * 1)(3),
                            (vp * 1)(init)))
    a = make_nd(np.full((2, 2), 1.5))
    b = make_nd(np.full((2, 2), 2.0))
    check(lib.MXKVStorePush(kv, 2, (ctypes.c_int * 2)(3, 3),
                            (vp * 2)(a, b), 0))
    out = make_nd(np.zeros((2, 2)))
    ovals = (vp * 1)(out)
    check(lib.MXKVStorePull(kv, 1, (ctypes.c_int * 1)(3), ovals, 0))
    res = np.zeros((2, 2), np.float32)
    check(lib.MXNDArraySyncCopyToCPU(out, res.ctypes.data_as(vp),
                                     res.size))
    np.testing.assert_allclose(res, 3.5)       # multi-device reduce
    check(lib.MXKVStoreBarrier(kv))
    # cross-check through the PYTHON frontend: same store semantics
    import mxnet_tpu as mx2
    pykv = mx2.kv.create("local")
    pykv.init(3, mx2.nd.zeros((2, 2)))
    pykv.push(3, [mx2.nd.full((2, 2), 1.5), mx2.nd.full((2, 2), 2.0)])
    np.testing.assert_allclose(pykv.pull(3).asnumpy(), res)
    # error surface
    rc = lib.MXKVStorePull(kv, 1, (ctypes.c_int * 1)(99), ovals, 0)
    assert rc != 0 and b"not initialized" in lib.MXNDGetLastError()
    for h in (init, a, b, out):
        lib.MXNDArrayFree(h)
    lib.MXKVStoreFree(kv)


def _train_symbol_json():
    """Least-squares regression graph for the C training slice: inputs in
    list_inputs() order (the MXInvokeCachedOp binding contract) must be
    [x, w, y]."""
    import mxnet_tpu.symbol as sym
    x = sym.Variable("x")
    w = sym.Variable("w")
    y = sym.Variable("y")
    fc = sym.FullyConnected(x, w, num_hidden=1, no_bias=True)
    loss = sym.mean(sym.square(fc - y))
    assert loss.list_inputs() == ["x", "w", "y"]
    return loss.tojson()


def test_autograd_cachedop_abi_in_process():
    """The C training loop through ctypes: MXCreateCachedOpFromJSON +
    MXAutogradMarkVariables/SetIsRecording/Backward + in-place sgd_update
    via MXImperativeInvoke — loss must decrease and the gradient must land
    in the caller's grad buffer."""
    lib = native.load_ndarray()
    u32, vp = ctypes.c_uint32, ctypes.c_void_p

    def make(shape_t, values):
        sh = (u32 * len(shape_t))(*shape_t)
        h = vp()
        assert lib.MXNDArrayCreate(sh, len(shape_t), 1, 0, 0,
                                   ctypes.byref(h)) == 0, \
            lib.MXNDGetLastError()
        arr = np.ascontiguousarray(values, np.float32)
        assert lib.MXNDArraySyncCopyFromCPU(
            h, arr.ctypes.data_as(vp), arr.size) == 0
        return h

    def read(h, shape_t):
        buf = np.empty(shape_t, np.float32)
        assert lib.MXNDArraySyncCopyToCPU(
            h, buf.ctypes.data_as(vp), buf.size) == 0
        return buf

    cop = vp()
    assert lib.MXCreateCachedOpFromJSON(
        _train_symbol_json().encode(), ctypes.byref(cop)) == 0, \
        lib.MXNDGetLastError()

    rng = np.random.default_rng(5)
    x_np = rng.standard_normal((8, 3)).astype(np.float32)
    w_true = np.array([[1.5, -2.0, 0.5]], np.float32)
    y_np = x_np @ w_true.T
    hx = make((8, 3), x_np)
    hw = make((1, 3), np.zeros((1, 3), np.float32))
    hy = make((8, 1), y_np)
    hg = make((1, 3), np.zeros((1, 3), np.float32))
    hlr = make((1,), np.array([0.4], np.float32))

    mark_vars = (vp * 1)(hw)
    reqs = (u32 * 1)(1)                       # write
    grads = (vp * 1)(hg)
    assert lib.MXAutogradMarkVariables(1, mark_vars, reqs, grads) == 0, \
        lib.MXNDGetLastError()

    prev = ctypes.c_int(-1)
    assert lib.MXAutogradSetIsRecording(1, ctypes.byref(prev)) == 0
    assert prev.value == 0

    op = vp()
    assert lib.NNGetOpHandle(b"sgd_update", ctypes.byref(op)) == 0

    losses = []
    for step in range(12):
        ins = (vp * 3)(hx, hw, hy)
        n_out = ctypes.c_int(0)
        outs = ctypes.POINTER(vp)()
        assert lib.MXInvokeCachedOp(cop, 3, ins, ctypes.byref(n_out),
                                    ctypes.byref(outs)) == 0, \
            lib.MXNDGetLastError()
        assert n_out.value == 1
        h_loss = outs[0]
        heads = (vp * 1)(h_loss)
        assert lib.MXAutogradBackward(1, heads, None, 0) == 0, \
            lib.MXNDGetLastError()
        losses.append(float(read(h_loss, ())))
        if step == 0:
            # analytic dL/dW for the first step (W=0): -2/N * (y^T x)
            expect = -2.0 / 8.0 * (y_np.T @ x_np)
            np.testing.assert_allclose(read(hg, (1, 3)), expect,
                                       rtol=1e-4, atol=1e-5)
        lib.MXNDArrayFree(h_loss)
        # in-place sgd_update(w, grad, lr, out=w)
        uins = (vp * 3)(hw, hg, hlr)
        uouts_arr = (vp * 1)(hw)
        uouts = ctypes.cast(uouts_arr, ctypes.POINTER(vp))
        un = ctypes.c_int(1)
        assert lib.MXImperativeInvoke(op, 3, uins, ctypes.byref(un),
                                      ctypes.byref(uouts), 0, None,
                                      None) == 0, lib.MXNDGetLastError()
    assert lib.MXAutogradSetIsRecording(0, ctypes.byref(prev)) == 0
    assert prev.value == 1
    assert losses[-1] < 0.05 * losses[0], losses
    # the trained weight approached the generator
    np.testing.assert_allclose(read(hw, (1, 3)), w_true, atol=0.2)
    lib.MXFreeCachedOp(cop)
    for h in (hx, hw, hy, hg, hlr):
        lib.MXNDArrayFree(h)


def test_cachedop_abi_accepts_symbol_handle():
    """MXCreateCachedOp consumes a SymbolHandle minted by the SYMBOL-slice
    library — the shared PyObject*-first handle-layout contract between
    the ABI .so files (one embedded interpreter per process)."""
    libs = native.load_symbol()
    libn = native.load_ndarray()
    vp = ctypes.c_void_p
    sh = vp()
    assert libs.MXSymbolCreateFromJSON(
        _train_symbol_json().encode(), ctypes.byref(sh)) == 0, \
        libs.MXSymGetLastError()
    cop = vp()
    assert libn.MXCreateCachedOp(sh, ctypes.byref(cop)) == 0, \
        libn.MXNDGetLastError()
    # drive one forward to prove the graph is live
    u32 = ctypes.c_uint32

    def make(shape_t, values):
        shp = (u32 * len(shape_t))(*shape_t)
        h = vp()
        assert libn.MXNDArrayCreate(shp, len(shape_t), 1, 0, 0,
                                    ctypes.byref(h)) == 0
        arr = np.ascontiguousarray(values, np.float32)
        assert libn.MXNDArraySyncCopyFromCPU(
            h, arr.ctypes.data_as(vp), arr.size) == 0
        return h

    hx = make((2, 3), np.ones((2, 3), np.float32))
    hw = make((1, 3), np.full((1, 3), 2.0, np.float32))
    hy = make((2, 1), np.zeros((2, 1), np.float32))
    ins = (vp * 3)(hx, hw, hy)
    n_out = ctypes.c_int(0)
    outs = ctypes.POINTER(vp)()
    assert libn.MXInvokeCachedOp(cop, 3, ins, ctypes.byref(n_out),
                                 ctypes.byref(outs)) == 0, \
        libn.MXNDGetLastError()
    buf = np.empty((), np.float32)
    assert libn.MXNDArraySyncCopyToCPU(
        outs[0], buf.ctypes.data_as(vp), 1) == 0
    # mean(square(1·[2,2,2] - 0)) = 36
    assert abs(float(buf) - 36.0) < 1e-4
    libn.MXFreeCachedOp(cop)
    libs.MXSymbolFree(sh)


TRAIN_C_HOST = r"""
/* Pure-C training loop: no Python linkage.  argv[1] = libmxtpu_ndarray.so,
   argv[2] = symbol JSON file (least-squares graph, inputs x/w/y).
   create arrays -> CachedOp forward -> autograd backward -> in-place
   sgd_update -> assert the loss decreased. */
#include <dlfcn.h>
#include <stdint.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
typedef int (*create_fn)(const uint32_t*, uint32_t, int, int, int, void**);
typedef int (*copyfrom_fn)(void*, const void*, size_t);
typedef int (*copyto_fn)(void*, void*, size_t);
typedef int (*ophandle_fn)(const char*, void**);
typedef int (*invoke_fn)(void*, int, void**, int*, void***, int,
                         const char**, const char**);
typedef int (*free_fn)(void*);
typedef const char* (*err_fn)(void);
typedef int (*setflag_fn)(int, int*);
typedef int (*mark_fn)(uint32_t, void**, uint32_t*, void**);
typedef int (*backward_fn)(uint32_t, void**, void**, int);
typedef int (*cop_json_fn)(const char*, void**);
typedef int (*cop_invoke_fn)(void*, int, void**, int*, void***);
int main(int argc, char** argv) {
  if (argc < 3) return 2;
  void* so = dlopen(argv[1], RTLD_NOW | RTLD_GLOBAL);
  if (!so) { fprintf(stderr, "%s\n", dlerror()); return 2; }
  create_fn nd_create = (create_fn)dlsym(so, "MXNDArrayCreate");
  copyfrom_fn nd_from = (copyfrom_fn)dlsym(so, "MXNDArraySyncCopyFromCPU");
  copyto_fn nd_to = (copyto_fn)dlsym(so, "MXNDArraySyncCopyToCPU");
  ophandle_fn op_get = (ophandle_fn)dlsym(so, "NNGetOpHandle");
  invoke_fn invoke = (invoke_fn)dlsym(so, "MXImperativeInvoke");
  free_fn nd_free = (free_fn)dlsym(so, "MXNDArrayFree");
  err_fn lasterr = (err_fn)dlsym(so, "MXNDGetLastError");
  setflag_fn set_rec = (setflag_fn)dlsym(so, "MXAutogradSetIsRecording");
  mark_fn mark = (mark_fn)dlsym(so, "MXAutogradMarkVariables");
  backward_fn backward = (backward_fn)dlsym(so, "MXAutogradBackward");
  cop_json_fn cop_create = (cop_json_fn)dlsym(so, "MXCreateCachedOpFromJSON");
  cop_invoke_fn cop_invoke = (cop_invoke_fn)dlsym(so, "MXInvokeCachedOp");
  free_fn cop_free = (free_fn)dlsym(so, "MXFreeCachedOp");
  if (!set_rec || !mark || !backward || !cop_create || !cop_invoke) {
    fprintf(stderr, "training symbols missing\n"); return 2; }

  /* read the symbol JSON */
  FILE* f = fopen(argv[2], "rb");
  if (!f) return 2;
  fseek(f, 0, SEEK_END); long sz = ftell(f); fseek(f, 0, SEEK_SET);
  char* json = (char*)malloc(sz + 1);
  if (fread(json, 1, sz, f) != (size_t)sz) return 2;
  json[sz] = 0; fclose(f);

  void* cop = NULL;
  if (cop_create(json, &cop)) {
    fprintf(stderr, "cachedop: %s\n", lasterr()); return 1; }

  /* y = x * 3 - 1ish data; fit w (1x2) from zero */
  uint32_t sx[2] = {4, 2}, sw[2] = {1, 2}, sy[2] = {4, 1}, sl[1] = {1};
  void *hx = NULL, *hw = NULL, *hy = NULL, *hg = NULL, *hlr = NULL;
  if (nd_create(sx, 2, 1, 0, 0, &hx) || nd_create(sw, 2, 1, 0, 0, &hw) ||
      nd_create(sy, 2, 1, 0, 0, &hy) || nd_create(sw, 2, 1, 0, 0, &hg) ||
      nd_create(sl, 1, 1, 0, 0, &hlr)) {
    fprintf(stderr, "create: %s\n", lasterr()); return 1; }
  float x[8] = {1, 0, 0, 1, 1, 1, -1, 2};
  float w0[2] = {0, 0};
  float y[4] = {3, -1, 2, -5};  /* generated by w* = [3, -1] */
  float lr[1] = {0.2f};
  if (nd_from(hx, x, 8) || nd_from(hw, w0, 2) || nd_from(hy, y, 4) ||
      nd_from(hg, w0, 2) || nd_from(hlr, lr, 1)) return 1;

  void* vars[1]; vars[0] = hw;
  uint32_t reqs[1] = {1};             /* kWriteTo */
  void* grads[1]; grads[0] = hg;
  if (mark(1, vars, reqs, grads)) {
    fprintf(stderr, "mark: %s\n", lasterr()); return 1; }
  int prev = -1;
  if (set_rec(1, &prev)) return 1;

  void* sgd = NULL;
  if (op_get("sgd_update", &sgd)) return 1;

  float first = -1, last = -1;
  for (int step = 0; step < 60; ++step) {
    void* ins[3]; ins[0] = hx; ins[1] = hw; ins[2] = hy;
    int n_out = 0; void** outs = NULL;
    if (cop_invoke(cop, 3, ins, &n_out, &outs) || n_out != 1) {
      fprintf(stderr, "forward: %s\n", lasterr()); return 1; }
    void* hloss = outs[0];
    void* heads[1]; heads[0] = hloss;
    if (backward(1, heads, NULL, 0)) {
      fprintf(stderr, "backward: %s\n", lasterr()); return 1; }
    float lv = 0;
    if (nd_to(hloss, &lv, 1)) return 1;
    if (step == 0) first = lv;
    last = lv;
    nd_free(hloss);
    /* in-place sgd_update(w, grad, lr) -> w */
    void* uins[3]; uins[0] = hw; uins[1] = hg; uins[2] = hlr;
    void* uouts_store[1]; uouts_store[0] = hw;
    void** uouts = uouts_store;
    int un = 1;
    if (invoke(sgd, 3, uins, &un, &uouts, 0, NULL, NULL)) {
      fprintf(stderr, "sgd: %s\n", lasterr()); return 1; }
  }
  if (set_rec(0, &prev) || prev != 1) return 1;
  if (!(last < 0.05f * first)) {
    fprintf(stderr, "loss did not decrease: %f -> %f\n", first, last);
    return 1;
  }
  float wfit[2];
  if (nd_to(hw, wfit, 2)) return 1;
  if (!(wfit[0] > 2.0f && wfit[0] < 4.0f && wfit[1] > -2.0f
        && wfit[1] < 0.0f)) {
    fprintf(stderr, "weights off: %f %f\n", wfit[0], wfit[1]);
    return 1;
  }
  cop_free(cop);
  nd_free(hx); nd_free(hw); nd_free(hy); nd_free(hg); nd_free(hlr);
  printf("TRAIN-C-HOST-OK loss %f -> %f w=[%f,%f]\n",
         first, last, wfit[0], wfit[1]);
  return 0;
}
"""


def test_training_abi_from_pure_c_host(tmp_path):
    """A C binary with no Python linkage runs a COMPLETE training step
    loop through the ABI — the reference's Scala/Horovod integration
    story (create arrays -> CachedOp forward -> MXAutogradBackward ->
    in-place sgd_update) — and the loss decreases."""
    if shutil.which("gcc") is None:
        pytest.skip("no C compiler")
    native.load_ndarray()
    so = os.path.join(os.path.dirname(native.__file__),
                      "libmxtpu_ndarray.so")
    jpath = tmp_path / "train_sym.json"
    jpath.write_text(_train_symbol_json())
    csrc = tmp_path / "train_host.c"
    csrc.write_text(TRAIN_C_HOST)
    exe = str(tmp_path / "train_host")
    subprocess.run(["gcc", "-O2", "-o", exe, str(csrc), "-ldl"],
                   check=True)
    env = dict(os.environ,
               JAX_PLATFORMS="cpu")
    r = subprocess.run([exe, so, str(jpath)], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr + r.stdout
    assert "TRAIN-C-HOST-OK" in r.stdout


def test_dataiter_abi_csv(tmp_path):
    """MXDataIter* through ctypes: create a CSVIter from string params,
    iterate batches, read data/label through shared NDArray handles,
    check reset (BeforeFirst) and the end-of-epoch Next()=0 contract."""
    lib = native.load_ndarray()
    u32, vp = ctypes.c_uint32, ctypes.c_void_p

    rng = np.random.default_rng(3)
    data = rng.standard_normal((10, 4)).astype(np.float32)
    labels = np.arange(10, dtype=np.float32).reshape(10, 1)
    dcsv = tmp_path / "d.csv"
    lcsv = tmp_path / "l.csv"
    np.savetxt(dcsv, data, delimiter=",")
    np.savetxt(lcsv, labels, delimiter=",")

    n = u32()
    creators = ctypes.POINTER(vp)()
    assert lib.MXListDataIters(ctypes.byref(n), ctypes.byref(creators)) \
        == 0
    names = [ctypes.cast(creators[i], ctypes.c_char_p).value
             for i in range(n.value)]
    assert b"CSVIter" in names
    creator = creators[names.index(b"CSVIter")]

    keys = (ctypes.c_char_p * 4)(b"data_csv", b"label_csv",
                                 b"data_shape", b"batch_size")
    vals = (ctypes.c_char_p * 4)(str(dcsv).encode(), str(lcsv).encode(),
                                 b"(4,)", b"5")
    it = vp()
    assert lib.MXDataIterCreateIter(creator, 4, keys, vals,
                                    ctypes.byref(it)) == 0, \
        lib.MXNDGetLastError()

    def read_all():
        assert lib.MXDataIterBeforeFirst(it) == 0
        got_d, got_l = [], []
        has = ctypes.c_int(0)
        while True:
            assert lib.MXDataIterNext(it, ctypes.byref(has)) == 0, \
                lib.MXNDGetLastError()
            if not has.value:
                break
            hd, hl = vp(), vp()
            assert lib.MXDataIterGetData(it, ctypes.byref(hd)) == 0, \
                lib.MXNDGetLastError()
            assert lib.MXDataIterGetLabel(it, ctypes.byref(hl)) == 0
            buf = np.empty((5, 4), np.float32)
            assert lib.MXNDArraySyncCopyToCPU(
                hd, buf.ctypes.data_as(vp), buf.size) == 0
            lbuf = np.empty((5, 1), np.float32)
            assert lib.MXNDArraySyncCopyToCPU(
                hl, lbuf.ctypes.data_as(vp), lbuf.size) == 0
            pad = ctypes.c_int(-1)
            assert lib.MXDataIterGetPadNum(it, ctypes.byref(pad)) == 0
            got_d.append(buf.copy())
            got_l.append(lbuf.copy())
            # reference ownership: Get* handles are CALLER-owned
            lib.MXNDArrayFree(hd)
            lib.MXNDArrayFree(hl)
        return got_d, got_l

    d1, l1 = read_all()
    assert len(d1) == 2                       # 10 rows / batch 5
    np.testing.assert_allclose(np.concatenate(d1), data, rtol=1e-5)
    np.testing.assert_allclose(
        np.concatenate(l1).ravel(), labels.ravel(), rtol=1e-6)
    # reset replays the epoch identically
    d2, _ = read_all()
    np.testing.assert_array_equal(np.concatenate(d1),
                                  np.concatenate(d2))
    # unknown creator errors cleanly
    bad = vp()
    assert lib.MXDataIterCreateIter(
        ctypes.cast(ctypes.c_char_p(b"NoSuchIter"), vp), 0, None, None,
        ctypes.byref(bad)) != 0
    assert lib.MXDataIterFree(it) == 0


def test_dataiter_abi_imagerecord(tmp_path):
    """MXDataIter* drives the native ImageRecordIter: RecordIO file in,
    decoded image batches out through the C surface."""
    lib = native.load_ndarray()
    u32, vp = ctypes.c_uint32, ctypes.c_void_p
    rec = str(tmp_path / "t.rec")
    _make_rec(rec, n=12, h=60, w=60)

    keys = (ctypes.c_char_p * 4)(b"path_imgrec", b"data_shape",
                                 b"batch_size", b"shuffle")
    # dmlc-style lowercase boolean: the reference's parameter parser
    # accepts it, so the ABI's attr parser must too
    vals = (ctypes.c_char_p * 4)(rec.encode(), b"(3, 32, 32)", b"4",
                                 b"false")
    it = vp()
    assert lib.MXDataIterCreateIter(
        ctypes.cast(ctypes.c_char_p(b"ImageRecordIter"), vp), 4, keys,
        vals, ctypes.byref(it)) == 0, lib.MXNDGetLastError()
    has = ctypes.c_int(0)
    assert lib.MXDataIterNext(it, ctypes.byref(has)) == 0
    assert has.value == 1
    hd = vp()
    assert lib.MXDataIterGetData(it, ctypes.byref(hd)) == 0, \
        lib.MXNDGetLastError()
    ndim = u32()
    pdata = ctypes.POINTER(u32)()
    assert lib.MXNDArrayGetShape(hd, ctypes.byref(ndim),
                                 ctypes.byref(pdata)) == 0
    assert [pdata[i] for i in range(ndim.value)] == [4, 3, 32, 32]
    buf = np.empty((4, 3, 32, 32), np.float32)
    assert lib.MXNDArraySyncCopyToCPU(hd, buf.ctypes.data_as(vp),
                                      buf.size) == 0
    assert np.isfinite(buf).all() and buf.std() > 0
    lib.MXNDArrayFree(hd)          # caller-owned per reference contract
    assert lib.MXDataIterFree(it) == 0


def test_misc_runtime_abi(tmp_path):
    """MXGetVersion / MXRandomSeed / views (At/Slice/Reshape write
    through to the base) / MXNDArraySave+Load .params round-trip."""
    lib = native.load_ndarray()
    u32, vp = ctypes.c_uint32, ctypes.c_void_p

    ver = ctypes.c_int(0)
    assert lib.MXGetVersion(ctypes.byref(ver)) == 0
    assert ver.value >= 100                    # 0.1.0 -> 100

    assert lib.MXRandomSeed(42) == 0

    def make(shape_t, values):
        sh = (u32 * len(shape_t))(*shape_t)
        h = vp()
        assert lib.MXNDArrayCreate(sh, len(shape_t), 1, 0, 0,
                                   ctypes.byref(h)) == 0
        arr = np.ascontiguousarray(values, np.float32)
        assert lib.MXNDArraySyncCopyFromCPU(
            h, arr.ctypes.data_as(vp), arr.size) == 0
        return h

    def read(h, shape_t):
        buf = np.empty(shape_t, np.float32)
        assert lib.MXNDArraySyncCopyToCPU(
            h, buf.ctypes.data_as(vp), buf.size) == 0
        return buf

    base_np = np.arange(12, dtype=np.float32).reshape(3, 4)
    hb = make((3, 4), base_np)

    # At: row view shares storage — write through it, base sees it
    hrow = vp()
    assert lib.MXNDArrayAt(hb, 1, ctypes.byref(hrow)) == 0, \
        lib.MXNDGetLastError()
    np.testing.assert_array_equal(read(hrow, (4,)), base_np[1])
    new_row = np.full(4, 99.0, np.float32)
    assert lib.MXNDArraySyncCopyFromCPU(
        hrow, new_row.ctypes.data_as(vp), 4) == 0
    assert (read(hb, (3, 4))[1] == 99.0).all()

    # Slice
    hs = vp()
    assert lib.MXNDArraySlice(hb, 1, 3, ctypes.byref(hs)) == 0
    got = read(hs, (2, 4))
    assert (got[0] == 99.0).all()

    # Reshape view
    hr = vp()
    dims = (ctypes.c_int * 2)(4, 3)
    assert lib.MXNDArrayReshape(hb, 2, dims, ctypes.byref(hr)) == 0
    assert read(hr, (4, 3)).shape == (4, 3)

    # Save + Load round trip (named)
    fname = str(tmp_path / "arrs.params").encode()
    handles = (vp * 2)(hb, hs)
    keys = (ctypes.c_char_p * 2)(b"base", b"slice")
    assert lib.MXNDArraySave(fname, 2, handles, keys) == 0, \
        lib.MXNDGetLastError()
    n_out, n_names = u32(), u32()
    arrs = ctypes.POINTER(vp)()
    names = ctypes.POINTER(ctypes.c_char_p)()
    assert lib.MXNDArrayLoad(fname, ctypes.byref(n_out),
                             ctypes.byref(arrs), ctypes.byref(n_names),
                             ctypes.byref(names)) == 0, \
        lib.MXNDGetLastError()
    assert n_out.value == 2 and n_names.value == 2
    loaded = {names[i]: arrs[i] for i in range(2)}
    np.testing.assert_array_equal(read(loaded[b"base"], (3, 4)),
                                  read(hb, (3, 4)))
    # the loaded .params round-trips through the PYTHON loader too
    import mxnet_tpu as mx2
    d = mx2.nd.load(fname.decode())
    assert set(d) == {"base", "slice"}
    # loaded handles are CALLER-owned (reference contract) — free them
    for i in range(2):
        lib.MXNDArrayFree(arrs[i])
    # duplicate keys must error, not silently drop arrays
    dup = (ctypes.c_char_p * 2)(b"w", b"w")
    assert lib.MXNDArraySave(fname, 2, handles, dup) != 0
    assert b"duplicate" in lib.MXNDGetLastError()
    for h in (hrow, hs, hr, hb):
        lib.MXNDArrayFree(h)


def test_symbol_introspection_abi():
    """MXSymbolListAtomicSymbolCreators / GetAtomicSymbolName /
    GetAtomicSymbolInfo — the wrapper-generation surface the reference's
    language bindings read at build time."""
    lib = native.load_symbol()
    u32, vp = ctypes.c_uint32, ctypes.c_void_p
    n = u32()
    creators = ctypes.POINTER(vp)()
    assert lib.MXSymbolListAtomicSymbolCreators(
        ctypes.byref(n), ctypes.byref(creators)) == 0, \
        lib.MXSymGetLastError()
    assert n.value >= 400
    names = [ctypes.cast(creators[i], ctypes.c_char_p).value
             for i in range(n.value)]
    assert b"Convolution" in names and b"sgd_update" in names

    idx = names.index(b"Convolution")
    got = ctypes.c_char_p()
    assert lib.MXSymbolGetAtomicSymbolName(creators[idx],
                                           ctypes.byref(got)) == 0
    assert got.value == b"Convolution"

    name = ctypes.c_char_p()
    desc = ctypes.c_char_p()
    num_args = u32()
    strs = ctypes.POINTER(ctypes.c_char_p)
    argn, argt, argd = strs(), strs(), strs()
    kv = ctypes.c_char_p()
    assert lib.MXSymbolGetAtomicSymbolInfo(
        creators[idx], ctypes.byref(name), ctypes.byref(desc),
        ctypes.byref(num_args), ctypes.byref(argn), ctypes.byref(argt),
        ctypes.byref(argd), ctypes.byref(kv)) == 0, \
        lib.MXSymGetLastError()
    assert name.value == b"Convolution"
    args = [argn[i] for i in range(num_args.value)]
    types = [argt[i] for i in range(num_args.value)]
    # tensor inputs lead (reference arguments convention), then params
    assert args[:3] == [b"data", b"weight", b"bias"]
    assert types[0] == b"NDArray-or-Symbol"
    assert b"kernel" in args and b"num_filter" in args
    # required/optional annotations derived from maker defaults
    assert any(t.startswith(b"any, required") or b"optional" in t
               for t in types)
    # variadic marker (reference key_var_num_args contract)
    idx_c = names.index(b"concat")
    assert lib.MXSymbolGetAtomicSymbolInfo(
        creators[idx_c], ctypes.byref(name), ctypes.byref(desc),
        ctypes.byref(num_args), ctypes.byref(argn), ctypes.byref(argt),
        ctypes.byref(argd), ctypes.byref(kv)) == 0
    assert kv.value == b"num_args"
