"""Predictor deployment surface, visualization, log parsing, launcher
env plumbing (reference: c_predict_api.cc, visualization.py,
tools/parse_log.py, tools/launch.py)."""
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx


def _train_tiny(tmp_path):
    rs = np.random.RandomState(0)
    X = rs.randn(60, 6).astype("float32")
    w = rs.randn(6, 3).astype("float32")
    y = (X @ w).argmax(axis=1).astype("float32")
    it = mx.io.NDArrayIter(X, y, batch_size=20)
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=3,
                              name="fc"),
        name="softmax", normalization="batch")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=5, optimizer="sgd",
            initializer=mx.init.Xavier(),
            optimizer_params={"learning_rate": 0.5})
    prefix = str(tmp_path / "tiny")
    mod.save_checkpoint(prefix, 5)
    return prefix, X, mod


def test_predictor_from_checkpoint(tmp_path):
    prefix, X, mod = _train_tiny(tmp_path)
    pred = mx.Predictor.load(prefix, 5, {"data": (10, 6)})
    pred.set_input("data", X[:10])
    pred.forward()
    out = pred.get_output(0)
    assert out.shape == (10, 3)

    # matches the training module's forward
    mod_out = []
    it = mx.io.NDArrayIter(X[:10], np.zeros(10, "float32"),
                           batch_size=10)
    for b in it:
        mod.forward(b, is_train=False)
        mod_out.append(mod.get_outputs()[0].asnumpy())
    np.testing.assert_allclose(out, mod_out[0], rtol=1e-5, atol=1e-6)

    # error surface
    with pytest.raises(mx.base.MXNetError):
        pred.set_input("nope", X[:10])


def test_predictor_missing_params_raises(tmp_path):
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=3,
                                name="fc")
    with pytest.raises(mx.base.MXNetError):
        mx.Predictor(net.tojson(), {}, {"data": (2, 6)})


def test_print_summary_and_plot(capsys):
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(
            mx.sym.Activation(
                mx.sym.Convolution(mx.sym.Variable("data"), num_filter=8,
                                   kernel=(3, 3), name="c1"),
                act_type="relu"),
            num_hidden=10, name="fc1"), name="softmax")
    total = mx.viz.print_summary(net, shape={"data": (1, 3, 8, 8)})
    out = capsys.readouterr().out
    assert "c1" in out and "fc1" in out
    assert "(1, 8, 6, 6)" in out  # conv output shape column populated
    # conv: 8*3*3*3 + 8 ; fc: 10*(8*6*6) + 10
    assert total == 8 * 3 * 3 * 3 + 8 + 10 * 8 * 6 * 6 + 10

    dot = mx.viz.plot_network(net, shape={"data": (1, 3, 8, 8)})
    src = dot if isinstance(dot, str) else dot.source
    assert "digraph" in src and "c1" in src or "Convolution" in src


def test_parse_log(tmp_path):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import parse_log

    log = [
        "INFO Epoch[0] Batch [10] Speed: 100.0 samples/sec",
        "INFO Epoch[0] Batch [20] Speed: 200.0 samples/sec",
        "INFO Epoch[0] Train-accuracy=0.5",
        "INFO Epoch[0] Time cost=3.25",
        "INFO Epoch[1] Train-accuracy=0.75",
        "INFO Epoch[1] Validation-accuracy=0.7",
    ]
    rows = parse_log.parse(log)
    assert rows[0]["train-accuracy"] == 0.5
    assert rows[0]["time"] == 3.25
    assert rows[0]["speed"] == 150.0
    assert rows[1]["validation-accuracy"] == 0.7


def test_launcher_local_sets_env(tmp_path):
    tool = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "launch.py")
    script = tmp_path / "worker.py"
    # per-rank output files: concurrent workers sharing one pipe would
    # interleave mid-line
    script.write_text(
        "import os, sys\n"
        "rank = os.environ['MXNET_WORKER_ID']\n"
        "line = ' '.join(['RANK', rank, os.environ['MXNET_NUM_WORKERS'],\n"
        "                 'COORD' if os.environ.get('MXNET_COORDINATOR')\n"
        "                 else ''])\n"
        "with open(os.path.join(sys.argv[1], 'out_' + rank), 'w') as f:\n"
        "    f.write(line)\n")
    out = subprocess.run(
        [sys.executable, tool, "-n", "2", "--launcher", "local",
         sys.executable, str(script), str(tmp_path)],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    lines = sorted((tmp_path / ("out_%d" % r)).read_text()
                   for r in range(2))
    assert lines == ["RANK 0 2 COORD", "RANK 1 2 COORD"]


def test_rtc_pallas_kernel():
    """The MXRtc analogue: user-defined Pallas kernels run over NDArrays
    (interpret mode here, asked for by name; Mosaic is the default and
    needs a TPU)."""
    from mxnet_tpu.rtc import PallasKernel

    def body(x_ref, y_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0 + y_ref[...]

    x = np.random.RandomState(0).randn(16, 128).astype("float32")
    y = np.random.RandomState(1).randn(16, 128).astype("float32")
    k = PallasKernel(body, [((16, 128), "float32")], interpret=True)
    (out,) = k(mx.nd.array(x), mx.nd.array(y))
    np.testing.assert_allclose(out.asnumpy(), x * 2 + y, rtol=1e-6)

    # push() adapter writes into provided outputs
    dst = mx.nd.zeros((16, 128))
    k.push([mx.nd.array(x), mx.nd.array(y)], [dst])
    np.testing.assert_allclose(dst.asnumpy(), x * 2 + y, rtol=1e-6)


def test_predictor_export_bundle_roundtrip(tmp_path):
    prefix, X, mod = _train_tiny(tmp_path)
    pred = mx.Predictor.load(prefix, 5, {"data": (10, 6)})
    pred.set_input("data", X[:10])
    ref = np.asarray(pred.forward()[0].asnumpy())

    bundle = str(tmp_path / "tiny.mxtpu")
    pred.export(bundle)
    assert os.path.getsize(bundle) > 0

    served = mx.Predictor.load_exported(bundle)
    assert served.output_names == pred.output_names
    out = served.forward(data=X[:10])[0]
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(served.get_output(0), ref, rtol=1e-5,
                               atol=1e-6)
    with pytest.raises(mx.base.MXNetError):
        served.forward(bogus=X[:10])


def test_export_model_cli(tmp_path):
    prefix, X, mod = _train_tiny(tmp_path)
    out = str(tmp_path / "cli.mxtpu")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__), "..",
                                      "tools", "export_model.py"),
         "--prefix", prefix, "--epoch", "5", "--data-shape", "10,6",
         "--out", out],
        capture_output=True, text=True, env=env,
        cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert res.returncode == 0, res.stderr
    served = mx.Predictor.load_exported(out)
    assert served.forward(data=X[:10])[0].shape == (10, 3)


def test_ckpt_fsck_cli(tmp_path):
    """tools/ckpt_fsck.py offline audit: exit 0 on a healthy directory,
    exit 1 + problem report on a corrupted shard, and --quarantine
    renames the bad epoch so the next resume skips it."""
    import json

    from mxnet_tpu import checkpoint as ckpt

    d = str(tmp_path / "ckpt")
    mgr = ckpt.CheckpointManager(d, prefix="m")
    args = {"w": mx.nd.array(np.arange(12, dtype="float32").reshape(3, 4))}
    for epoch in (1, 2):
        mgr.save(arg_params=args, aux_params={}, epoch=epoch)

    tool = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "ckpt_fsck.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def run(*extra):
        return subprocess.run(
            [sys.executable, tool, d, "--prefix", "m", *extra],
            capture_output=True, text=True, env=env,
            cwd=os.path.join(os.path.dirname(__file__), ".."))

    res = run()
    assert res.returncode == 0, res.stderr
    report = json.loads(res.stdout)
    assert report["ok"] and len(report["epochs"]) == 2

    shard = os.path.join(d, "m-0002.shard0.params")
    size = os.path.getsize(shard)
    with open(shard, "r+b") as f:
        f.seek(size // 2)
        b = f.read(1)
        f.seek(size // 2)
        f.write(bytes([b[0] ^ 0x01]))

    res = run()
    assert res.returncode == 1, res.stdout
    report = json.loads(res.stdout)
    bad = [e for e in report["epochs"] if not e["ok"]]
    assert len(bad) == 1 and bad[0]["epoch"] == 2

    res = run("--quarantine")
    assert res.returncode == 1
    assert ckpt.CheckpointManager(d, prefix="m").epochs() == [1]
    res = run()
    assert res.returncode == 0, res.stdout


def test_c_predict_api(tmp_path):
    """Build src/c_predict_api.cc, compile a C client against the shipped
    header, and serve a checkpoint from C — the reference's
    c_predict_api.cc contract (create/set-input/forward/get-output)."""
    import shutil

    if shutil.which("g++") is None:
        pytest.skip("no g++")
    from mxnet_tpu import _native

    lib = _native._load("c_predict_api")
    if lib is None:
        pytest.skip("c_predict_api did not build (no libpython?)")

    prefix, X, mod = _train_tiny(tmp_path)
    # reference clients read the raw files
    with open(prefix + "-symbol.json") as f:
        sym_json = f.read()
    ref = mx.Predictor.load(prefix, 5, {"data": (4, 6)})
    ref.set_input("data", X[:4])
    expected = ref.forward()[0].asnumpy()

    repo = os.path.join(os.path.dirname(__file__), "..")
    c_src = tmp_path / "client.c"
    c_src.write_text(r'''
#include <stdio.h>
#include <stdlib.h>
#include "mxnet_tpu/c_predict_api.h"

int main(int argc, char** argv) {
    FILE* f = fopen(argv[1], "r");           /* symbol json */
    char* json = (char*)malloc(1 << 20);
    size_t n = fread(json, 1, 1 << 20, f); json[n] = 0; fclose(f);
    f = fopen(argv[2], "rb");                /* params blob */
    char* params = (char*)malloc(1 << 24);
    long psize = (long)fread(params, 1, 1 << 24, f); fclose(f);
    f = fopen(argv[3], "rb");                /* input floats */
    float in[24];
    if (fread(in, sizeof(float), 24, f) != 24) return 9;
    fclose(f);

    const char* keys[] = {"data"};
    mx_uint indptr[] = {0, 2};
    mx_uint shape[] = {4, 6};
    PredictorHandle h;
    if (MXPredCreate(json, params, (int)psize, 1, 0, 1, keys, indptr,
                     shape, &h)) {
        fprintf(stderr, "create: %s\n", MXGetLastError()); return 1;
    }
    if (MXPredSetInput(h, "data", in, 24)) {
        fprintf(stderr, "set: %s\n", MXGetLastError()); return 2;
    }
    if (MXPredForward(h)) {
        fprintf(stderr, "fwd: %s\n", MXGetLastError()); return 3;
    }
    mx_uint *oshape, ondim;
    if (MXPredGetOutputShape(h, 0, &oshape, &ondim)) return 4;
    if (ondim != 2 || oshape[0] != 4 || oshape[1] != 3) return 5;
    float out[12];
    if (MXPredGetOutput(h, 0, out, 12)) {
        fprintf(stderr, "get: %s\n", MXGetLastError()); return 6;
    }
    for (int i = 0; i < 12; i++) printf("%.6f\n", out[i]);
    MXPredFree(h);
    return 0;
}
''')
    exe = tmp_path / "client"
    so = os.path.join(repo, "mxnet_tpu", "_build", "c_predict_api.so")
    res = subprocess.run(
        ["g++", str(c_src), so, "-I", os.path.join(repo, "include"),
         "-o", str(exe)], capture_output=True, text=True)
    assert res.returncode == 0, res.stderr

    X[:4].astype("float32").tofile(tmp_path / "input.bin")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_TPU_HOME=os.path.abspath(repo),
               LD_LIBRARY_PATH=os.path.dirname(so))
    res = subprocess.run(
        [str(exe), prefix + "-symbol.json", prefix + "-0005.params",
         str(tmp_path / "input.bin")],
        capture_output=True, text=True, env=env, timeout=300)
    assert res.returncode == 0, (res.returncode, res.stderr)
    got = np.array([float(x) for x in res.stdout.split()],
                   "float32").reshape(4, 3)
    # the C process runs with default matmul precision (no conftest)
    np.testing.assert_allclose(got, expected, rtol=5e-3, atol=1e-3)


def test_cpp_api_client(tmp_path):
    """The expanded C ABI (VERDICT r3 task 3): compile the cpp-package
    example — symbol composition through the registry-generated C++ op
    frontend, shape inference, executor bind, fwd/bwd TRAINING with the
    fused sgd_update invoked imperatively, scoring, JSON round-trip —
    and require it to reach >0.9 accuracy, all from one C++ binary.

    Reference: include/mxnet/c_api.h groups NDArray/Symbol/Executor +
    cpp-package/example/mlp.cpp."""
    import shutil

    if shutil.which("g++") is None:
        pytest.skip("no g++")
    from mxnet_tpu import _native

    lib = _native._load("c_api")
    if lib is None:
        pytest.skip("c_api did not build (no libpython?)")

    repo = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    # the generated op frontend must be fresh w.r.t. the registry
    gen = subprocess.run(
        [sys.executable, os.path.join(repo, "tools",
                                      "gen_cpp_package.py"),
         "-o", str(tmp_path / "op.h")],
        capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=300)
    assert gen.returncode == 0, gen.stdout + gen.stderr
    committed = open(os.path.join(repo, "include", "mxnet_tpu", "cpp",
                                  "op.h")).read()
    assert committed == open(str(tmp_path / "op.h")).read(), \
        "include/mxnet_tpu/cpp/op.h is stale; re-run " \
        "tools/gen_cpp_package.py"

    so = os.path.join(repo, "mxnet_tpu", "_build", "c_api.so")
    exe = tmp_path / "cpp_client"
    res = subprocess.run(
        ["g++", "-O2", "-std=c++17",
         "-I", os.path.join(repo, "include"),
         os.path.join(repo, "examples", "deploy", "cpp_api", "main.cc"),
         so, "-Wl,-rpath," + os.path.dirname(so), "-o", str(exe)],
        capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr

    env = dict(os.environ, JAX_PLATFORMS="cpu", MXNET_TPU_HOME=repo,
               LD_LIBRARY_PATH=os.path.dirname(so))
    res = subprocess.run([str(exe)], capture_output=True, text=True,
                         env=env, timeout=600)
    assert res.returncode == 0, (res.returncode, res.stdout, res.stderr)
    assert "CPP API CLIENT OK" in res.stdout, res.stdout


def test_cpp_full_abi_client(tmp_path):
    """The round-5 C ABI closure (VERDICT r4 item 3): one C++ binary
    drives MXDataIter* (CSVIter from the creator registry),
    MXCreateCachedOp/MXInvokeCachedOp, MXAutograd* (mark variables +
    backward through the recorded CachedOp forward) and MXKVStore*
    (init/push/pull with a registered C updater) to train the MLP to
    >0.9 accuracy.

    Reference: include/mxnet/c_api.h groups :680-760 (autograd),
    :1400-1500 (data iter), :1513-1770 (kvstore),
    c_api_ndarray.cc:611-660 (CachedOp)."""
    import shutil

    if shutil.which("g++") is None:
        pytest.skip("no g++")
    from mxnet_tpu import _native

    lib = _native._load("c_api")
    if lib is None:
        pytest.skip("c_api did not build (no libpython?)")

    repo = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    so = os.path.join(repo, "mxnet_tpu", "_build", "c_api.so")
    exe = tmp_path / "full_abi_client"
    res = subprocess.run(
        ["g++", "-O2", "-std=c++17",
         "-I", os.path.join(repo, "include"),
         os.path.join(repo, "examples", "deploy", "cpp_api",
                      "full_abi.cc"),
         so, "-Wl,-rpath," + os.path.dirname(so), "-o", str(exe)],
        capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr

    env = dict(os.environ, JAX_PLATFORMS="cpu", MXNET_TPU_HOME=repo,
               LD_LIBRARY_PATH=os.path.dirname(so))
    res = subprocess.run([str(exe)], capture_output=True, text=True,
                         env=env, timeout=600, cwd=str(tmp_path))
    assert res.returncode == 0, (res.returncode, res.stdout, res.stderr)
    assert "FULL ABI CLIENT OK" in res.stdout, res.stdout


def test_c_predict_partial_out_and_ndlist(tmp_path):
    """Round-5 MXPred closure: MXPredCreatePartialOut exposes a named
    INTERNAL output (the pre-softmax fc head), MXPredPartialForward
    honors the stepping contract, and MXNDList* parses an nd.save
    container (the mean-image deployment artifact)."""
    import shutil

    if shutil.which("g++") is None:
        pytest.skip("no g++")
    from mxnet_tpu import _native

    lib = _native._load("c_predict_api")
    if lib is None:
        pytest.skip("c_predict_api did not build (no libpython?)")

    prefix, X, mod = _train_tiny(tmp_path)
    # expected internal feature: raw fc output (pre-softmax)
    ref = mx.Predictor.load(prefix, 5, {"data": (4, 6)})
    internals = ref._symbol.get_internals()
    names = internals.list_outputs()
    fc_idx = names.index("fc_output")
    fc_sym = internals[fc_idx]
    from mxnet_tpu.executor import _trace_fn
    import jax

    fn, _, _ = _trace_fn(fc_sym, is_train=False)
    args = {n: a._data for n, a in ref._exec.arg_dict.items()}
    args["data"] = mx.nd.array(X[:4])._data
    expected = np.asarray(
        fn(args, {n: a._data for n, a in ref._exec.aux_dict.items()},
           jax.random.PRNGKey(0))[0][0])

    # nd.save container for the NDList leg
    mean = mx.nd.array(np.arange(6, dtype="float32"))
    mx.nd.save(str(tmp_path / "mean.nd.npz"), {"mean_img": mean})

    repo = os.path.join(os.path.dirname(__file__), "..")
    c_src = tmp_path / "client2.c"
    c_src.write_text(r'''
#include <stdio.h>
#include <stdlib.h>
#include "mxnet_tpu/c_predict_api.h"

int main(int argc, char** argv) {
    FILE* f = fopen(argv[1], "r");
    char* json = (char*)malloc(1 << 20);
    size_t n = fread(json, 1, 1 << 20, f); json[n] = 0; fclose(f);
    f = fopen(argv[2], "rb");
    char* params = (char*)malloc(1 << 24);
    long psize = (long)fread(params, 1, 1 << 24, f); fclose(f);
    f = fopen(argv[3], "rb");
    float in[24];
    if (fread(in, sizeof(float), 24, f) != 24) return 9;
    fclose(f);
    f = fopen(argv[4], "rb");                /* ndlist blob */
    char* nd = (char*)malloc(1 << 20);
    long nsize = (long)fread(nd, 1, 1 << 20, f); fclose(f);

    const char* keys[] = {"data"};
    const char* outs[] = {"fc_output"};
    mx_uint indptr[] = {0, 2};
    mx_uint shape[] = {4, 6};
    PredictorHandle h;
    if (MXPredCreatePartialOut(json, params, (int)psize, 1, 0, 1, keys,
                               indptr, shape, 1, outs, &h)) {
        fprintf(stderr, "create: %s\n", MXGetLastError()); return 1;
    }
    if (MXPredSetInput(h, "data", in, 24)) return 2;
    int left = -1;
    if (MXPredPartialForward(h, 0, &left) || left != 0) return 3;
    mx_uint *oshape, ondim;
    if (MXPredGetOutputShape(h, 0, &oshape, &ondim)) return 4;
    if (ondim != 2 || oshape[0] != 4 || oshape[1] != 3) return 5;
    float out[12];
    if (MXPredGetOutput(h, 0, out, 12)) return 6;
    for (int i = 0; i < 12; i++) printf("%.6f\n", out[i]);
    MXPredFree(h);

    NDListHandle nl;
    mx_uint len = 0;
    if (MXNDListCreate(nd, (int)nsize, &nl, &len) || len != 1) {
        fprintf(stderr, "ndlist: %s\n", MXGetLastError()); return 7;
    }
    const char* key; const float* data; const mx_uint* nshape;
    mx_uint nndim;
    if (MXNDListGet(nl, 0, &key, &data, &nshape, &nndim)) return 8;
    printf("NDLIST %s %u %u %.1f %.1f\n", key, nndim, nshape[0],
           data[0], data[5]);
    MXNDListFree(nl);
    return 0;
}
''')
    exe = tmp_path / "client2"
    so = os.path.join(repo, "mxnet_tpu", "_build", "c_predict_api.so")
    res = subprocess.run(
        ["g++", str(c_src), so, "-I", os.path.join(repo, "include"),
         "-o", str(exe)], capture_output=True, text=True)
    assert res.returncode == 0, res.stderr

    X[:4].astype("float32").tofile(tmp_path / "input.bin")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_TPU_HOME=os.path.abspath(repo),
               LD_LIBRARY_PATH=os.path.dirname(so))
    res = subprocess.run(
        [str(exe), prefix + "-symbol.json", prefix + "-0005.params",
         str(tmp_path / "input.bin"), str(tmp_path / "mean.nd.npz")],
        capture_output=True, text=True, env=env, timeout=300)
    assert res.returncode == 0, (res.returncode, res.stderr)
    lines = res.stdout.strip().splitlines()
    got = np.array([float(x) for x in lines[:12]],
                   "float32").reshape(4, 3)
    np.testing.assert_allclose(got, expected, rtol=5e-3, atol=1e-3)
    assert lines[12].startswith("NDLIST mean_img 1 6 0.0 5.0"), lines[12]


def test_autotune_report_cli(tmp_path):
    """tools/autotune.py --report pretty-prints stored records (stdlib
    only) and exits 1 with a hint on an empty store."""
    from mxnet_tpu import autotune

    d = str(tmp_path / "store")
    store = autotune.AutotuneStore(d)
    key = autotune.Key("serve", "aabbccddeeff", backend="cpu")
    store.put(key, {
        "kind": "serve", "fingerprint": "aabbccddeeff", "mesh": "-",
        "backend": "cpu",
        "knob_space": {"quant": ["", "int8"]},
        "knobs": {"quant": "int8", "buckets": [16, 64]},
        "metric": 1234.5, "baseline_metric": 1000.0,
        "speedup_vs_default": 1.2345, "measurements": 4,
        "trials": [], "elapsed_s": 2.5, "budget_exhausted": False,
        "created": time.time(),
    })

    tool = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "autotune.py")

    def run(directory):
        return subprocess.run(
            [sys.executable, tool, "--report", "--dir", directory],
            capture_output=True, text=True, timeout=60,
            cwd=os.path.join(os.path.dirname(__file__), ".."))

    res = run(d)
    assert res.returncode == 0, res.stderr
    out = res.stdout
    assert "serve" in out and "aabbccddeeff" in out
    assert "quant='int8'" in out and "buckets=[16, 64]" in out
    assert "1234" in out and "1.23x default" in out
    assert "4 measurements" in out

    empty = str(tmp_path / "empty")
    os.makedirs(empty)
    res = run(empty)
    assert res.returncode == 1
    assert "no autotune records" in res.stderr


def test_diagnose_cli_renders_gateway_incident(tmp_path):
    """tools/diagnose.py on a gateway incident artifact: recognized by
    kind, gathered by the directory glob, rendered with counters, the
    drain outcome, open connections, and the timeline."""
    import json

    payload = {
        "kind": "mxnet_tpu-gateway-incident",
        "pid": 4242, "time": time.time(),
        "host": "127.0.0.1", "port": 8431, "state": "draining",
        "counters": {"connections": 9, "requests": 7,
                     "streams_completed": 5, "shed_429": 1,
                     "unavailable_503": 0, "draining_503": 1,
                     "cancelled": 2, "slow_reader_sheds": 1,
                     "deadline_cancels": 0, "force_cancelled": 1,
                     "disconnects": 2, "idempotent_replays": 1},
        "open_connections": [
            {"rid": 31, "peer": "('127.0.0.1', 55021)",
             "tokens_sent": 3, "keyed": True, "orphaned": True}],
        "drain": {"requested": True, "deadline_s": 5.0, "clean": False},
        "timeline": [
            {"t": 0.01, "event": "start", "port": 8431},
            {"t": 2.5, "event": "sigterm"},
            {"t": 7.5, "event": "drain_end", "clean": False,
             "force_cancelled": 1,
             "detail": "grace lapsed with 1 stream open"}],
    }
    path = tmp_path / "gateway-incident-4242-1.json"
    path.write_text(json.dumps(payload))
    tool = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "diagnose.py")
    # the directory scan must pick the artifact up by its glob
    res = subprocess.run([sys.executable, tool, str(tmp_path)],
                         capture_output=True, text=True, timeout=60)
    assert res.returncode == 0, res.stderr
    out = res.stdout
    assert "GATEWAY INCIDENT" in out
    assert "127.0.0.1:8431" in out and "draining" in out
    assert "9 connection(s)" in out and "1 shed 429" in out
    assert "FORCED" in out  # the drain outcome line
    assert "rid 31" in out and "orphaned" in out  # open connections
    assert "sigterm" in out and "grace lapsed" in out  # timeline
    # an unrecognized directory still names the gateway artifact kind
    empty = str(tmp_path / "empty")
    os.makedirs(empty)
    res = subprocess.run([sys.executable, tool, empty],
                         capture_output=True, text=True, timeout=60)
    assert res.returncode == 1
    assert "gateway-incident" in res.stderr
