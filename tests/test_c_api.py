"""C ABI (libmxtpu) — build the library, compile a C host program against
include/mxtpu/c_api.h, and run it end-to-end in a clean environment.

Parity model: the reference's C ABI is its language-binding surface
(include/mxnet/c_api.h + src/c_api/c_api.cc); the capability under test is
"a C program can create arrays, invoke ops, read results, and get error
strings without any Python of its own"."""
import os
import shutil
import subprocess
import sysconfig

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _python_embed_flags():
    inc = sysconfig.get_paths()["include"]
    libdir = sysconfig.get_config_var("LIBDIR") or ""
    ver = sysconfig.get_config_var("LDVERSION") or \
        sysconfig.get_config_var("VERSION")
    return [f"-I{inc}"], [f"-L{libdir}", f"-lpython{ver}",
                          f"-Wl,-rpath,{libdir}"]


@pytest.fixture(scope="module")
def capi_lib(tmp_path_factory):
    gxx = shutil.which("g++")
    gcc = shutil.which("gcc") or gxx
    if gxx is None:
        pytest.skip("no g++ available")
    build = tmp_path_factory.mktemp("capi")
    lib = str(build / "libmxtpu.so")
    inc_flags, ld_flags = _python_embed_flags()
    subprocess.run(
        [gxx, "-O2", "-shared", "-fPIC", "-std=c++17",
         os.path.join(REPO, "mxnet_tpu", "native", "mxtpu_c_api.cc"),
         "-o", lib] + inc_flags + ld_flags,
        check=True, capture_output=True)
    exe = str(build / "smoke")
    subprocess.run(
        [gcc, os.path.join(REPO, "examples", "extensions", "c_binding",
                           "smoke.c"),
         "-I", os.path.join(REPO, "include"),
         "-L", str(build), "-lmxtpu", f"-Wl,-rpath,{build}", "-o", exe],
        check=True, capture_output=True)
    return exe


def test_c_host_program_end_to_end(capi_lib):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["MXTPU_PLATFORM"] = "cpu"
    proc = subprocess.run([capi_lib], capture_output=True, text=True,
                          timeout=600, env=env)
    assert proc.returncode == 0, f"stdout={proc.stdout}\nstderr={proc.stderr}"
    assert "C API OK" in proc.stdout
    # the ABI exposes the full op registry
    ops_line = [l for l in proc.stdout.splitlines() if l.startswith("ops=")]
    assert ops_line and int(ops_line[0].split("=")[1]) > 400




def _build_c_example(capi_lib, src_name, out_name, extra_flags=()):
    """Compile one examples/extensions/c_binding host program against
    the freshly-built libmxtpu (shared across the ABI fixtures)."""
    build = os.path.dirname(capi_lib)
    gcc = shutil.which("gcc") or shutil.which("g++")
    exe = os.path.join(build, out_name)
    subprocess.run(
        [gcc, os.path.join(REPO, "examples", "extensions", "c_binding",
                           src_name),
         "-I", os.path.join(REPO, "include"),
         "-L", build, "-lmxtpu", f"-Wl,-rpath,{build}",
         *extra_flags, "-o", exe],
        check=True, capture_output=True)
    return exe


@pytest.fixture(scope="module")
def predict_exe(capi_lib):
    return _build_c_example(capi_lib, "predict.c", "predict")


def test_predict_abi_end_to_end(predict_exe, tmp_path):
    """MXPredCreate/SetInput/Forward/GetOutput from pure C against a
    checkpoint produced by the Python frontend — the deployment handoff
    the reference's c_predict_api exists for. The C result must match the
    Python executor bit-for-bit (same executable)."""
    gen = (
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "import numpy as np\n"
        "import mxnet_tpu as mx\n"
        "data = mx.sym.var('data')\n"
        "net = mx.sym.FullyConnected(data, num_hidden=16, name='fc1')\n"
        "net = mx.sym.Activation(net, act_type='relu')\n"
        "net = mx.sym.FullyConnected(net, num_hidden=4, name='fc2')\n"
        "net = mx.sym.softmax(net)\n"
        "ex = net.simple_bind(mx.cpu(), data=(1, 8))\n"
        "rs = np.random.RandomState(7)\n"
        "args = {n: mx.nd.array(rs.randn(*a.shape).astype('f') * 0.3)\n"
        "        for n, a in ex.arg_dict.items() if n != 'data'}\n"
        "ex.copy_params_from(args)\n"
        "out = ex.forward(data=mx.nd.ones((1, 8)))[0].asnumpy()\n"
        "np.save(%r, out)\n"
        "from mxnet_tpu.model import save_checkpoint\n"
        "save_checkpoint(%r, 0, net, args, {})\n"
    )
    prefix = str(tmp_path / "mlp")
    ref_out = str(tmp_path / "ref.npy")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    subprocess.run([os.sys.executable, "-c", gen % (ref_out, prefix)],
                   check=True, env=env, timeout=300)
    import numpy as onp

    ref = onp.load(ref_out)
    env["MXTPU_PLATFORM"] = "cpu"
    proc = subprocess.run(
        [predict_exe, f"{prefix}-symbol.json", f"{prefix}-0000.params"],
        capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, f"{proc.stdout}\n{proc.stderr}"
    assert "PREDICT OK" in proc.stdout
    argmax_line = [l for l in proc.stdout.splitlines()
                   if l.startswith("argmax=")][0]
    c_argmax = int(argmax_line.split("=")[1].split()[0])
    c_sum = float(argmax_line.split("sum=")[1])
    assert c_argmax == int(ref.argmax())
    assert abs(c_sum - float(ref.sum())) < 1e-4  # softmax sums to 1


@pytest.fixture(scope="module")
def symbol_io_exe(capi_lib):
    return _build_c_example(capi_lib, "symbol_io.c", "symbol_io")


def test_symbol_and_container_abi(symbol_io_exe, tmp_path):
    """Symbol load/introspect/json-roundtrip, per-op schema info, and
    NDArray container save/load — all from pure C (parity:
    MXSymbolCreateFromJSON & co., MXNDArraySave/Load)."""
    gen = (
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "import mxnet_tpu as mx\n"
        "net = mx.sym.FullyConnected(mx.sym.var('data'), num_hidden=4)\n"
        "net = mx.sym.BatchNorm(net)\n"
        "net = mx.sym.softmax(net)\n"
        "net.save(%r)\n"
    )
    sym_path = str(tmp_path / "net-symbol.json")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    subprocess.run([os.sys.executable, "-c", gen % sym_path],
                   check=True, env=env, timeout=300)
    env["MXTPU_PLATFORM"] = "cpu"
    proc = subprocess.run(
        [symbol_io_exe, sym_path, str(tmp_path / "params.nd")],
        capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, \
        f"stdout={proc.stdout}\nstderr={proc.stderr}"
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("SYMBOL_IO_OK")][0]
    # data + fc weight/bias + bn gamma/beta (+2 aux moving stats)
    assert "args=5" in line and "aux=2" in line, line


@pytest.fixture(scope="module")
def multi_pred_exe(capi_lib):
    return _build_c_example(capi_lib, "multi_pred.c", "multi_pred",
                            extra_flags=("-pthread",))


def test_multi_threaded_inference_abi(multi_pred_exe, tmp_path):
    """Concurrent predictors from N host threads over one checkpoint —
    the reference's example/multi_threaded_inference capability. Each
    thread owns a PredictorHandle; all must produce identical results
    with no crashes or cross-talk."""
    gen = (
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "import numpy as np\n"
        "import mxnet_tpu as mx\n"
        "net = mx.sym.FullyConnected(mx.sym.var('data'), num_hidden=8,\n"
        "                            name='fc1')\n"
        "net = mx.sym.Activation(net, act_type='relu')\n"
        "net = mx.sym.softmax(mx.sym.FullyConnected(net, num_hidden=3,\n"
        "                                           name='fc2'))\n"
        "ex = net.simple_bind(mx.cpu(), data=(1, 8))\n"
        "rs = np.random.RandomState(3)\n"
        "args = {n: mx.nd.array(rs.randn(*a.shape).astype('f') * 0.3)\n"
        "        for n, a in ex.arg_dict.items() if n != 'data'}\n"
        "from mxnet_tpu.model import save_checkpoint\n"
        "save_checkpoint(%r, 0, net, args, {})\n"
    )
    prefix = str(tmp_path / "mlp")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    subprocess.run([os.sys.executable, "-c", gen % prefix],
                   check=True, env=env, timeout=300)
    env["MXTPU_PLATFORM"] = "cpu"
    proc = subprocess.run(
        [multi_pred_exe, prefix + "-symbol.json",
         prefix + "-0000.params", "4", "5"],
        capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, \
        f"stdout={proc.stdout}\nstderr={proc.stderr}"
    assert "MULTI_PRED_OK" in proc.stdout
