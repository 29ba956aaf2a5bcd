"""Round-4d: fleet PS accessors/role makers, UtilBase, LocalFS,
profiler SummaryView, device.cuda props, prim toggles, and a trained
seq2seq beam-decode journey."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed.fleet as fleet


def test_role_makers(monkeypatch):
    monkeypatch.setenv("TRAINING_ROLE", "PSERVER")
    monkeypatch.setenv("PADDLE_PSERVERS_IP_PORT_LIST",
                       "127.0.0.1:7164,127.0.0.1:7165")
    monkeypatch.setenv("POD_IP", "127.0.0.1")
    monkeypatch.setenv("PADDLE_PORT", "7165")
    rm = fleet.PaddleCloudRoleMaker(is_collective=False)
    assert rm.is_server() and not rm.is_worker()
    assert rm.server_index() == 1
    assert rm.server_num() == 2

    rm2 = fleet.UserDefinedRoleMaker(
        current_id=0, role=fleet.Role.WORKER, worker_num=2,
        server_endpoints=["127.0.0.1:7164"])
    assert rm2.is_worker() and rm2.worker_num() == 2
    assert rm2.get_pserver_endpoints() == ["127.0.0.1:7164"]


def test_fleet_ps_server_worker_roundtrip():
    from paddle_tpu.distributed.ps import PSServer, PSClient
    server = PSServer(port=0)
    server.create_dense_table("w", [4], rule="sgd", lr=0.1)
    client = PSClient([f"127.0.0.1:{server.port}"])
    before = np.asarray(client.pull_dense("w")).reshape(-1)
    np.testing.assert_allclose(before, np.zeros(4))
    client.push_dense("w", np.ones(4, np.float32))   # sgd: w -= lr*g
    got = np.asarray(client.pull_dense("w")).reshape(-1)
    np.testing.assert_allclose(got, -0.1 * np.ones(4), rtol=1e-6)
    client.close()


def test_fleet_accessors_collective_defaults():
    # no role maker registered -> collective behavior
    f = fleet.Fleet()
    assert f.is_worker() is True and f.is_server() is False
    assert f.server_num() == 0 and f.server_index() == -1
    assert f.server_endpoints() == []
    assert f.server_endpoints(to_string=True) == ""


def test_util_get_file_shard():
    u = fleet.UtilBase()
    files = [f"f{i}" for i in range(5)]
    # world size 1 in-process: full list
    assert u.get_file_shard(files) == files


def test_local_fs(tmp_path):
    fs = fleet.utils.LocalFS()
    d = tmp_path / "sub"
    fs.mkdirs(str(d))
    assert fs.is_dir(str(d)) and fs.is_exist(str(d))
    f = tmp_path / "a.txt"
    fs.touch(str(f))
    assert fs.is_file(str(f))
    dirs, files = fs.ls_dir(str(tmp_path))
    assert dirs == ["sub"] and files == ["a.txt"]
    fs.mv(str(f), str(tmp_path / "b.txt"))
    assert fs.is_exist(str(tmp_path / "b.txt"))
    fs.delete(str(d))
    assert not fs.is_exist(str(d))
    with pytest.raises(RuntimeError):
        fleet.utils.HDFSClient()


def test_meta_parallel_exports_and_sharding_wrapper():
    mp = fleet.meta_parallel
    assert hasattr(mp, "PipelineParallel")
    net = paddle.nn.Linear(4, 4)
    sp = mp.ShardingParallel(net)
    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    assert sp(x).shape == [2, 4]


def test_profiler_summary_view():
    import paddle_tpu.profiler as profiler
    assert profiler.SummaryView.KernelView.name == "KernelView"
    assert len(list(profiler.SummaryView)) >= 8


def test_device_cuda_props():
    cuda = paddle.device.cuda
    assert isinstance(cuda.get_device_name(), str)
    props = cuda.get_device_properties()
    assert props.name == cuda.get_device_name()
    assert cuda.get_device_capability() == (0, 0)
    with cuda.stream_guard(cuda.current_stream()):
        pass


def test_prim_toggles_and_incubate_grad():
    a = paddle.incubate.autograd
    assert not a.prim_enabled()
    a.enable_prim()
    try:
        assert a.prim_enabled()
    finally:
        a.disable_prim()
    assert not a.prim_enabled()
    x = paddle.to_tensor([3.0], stop_gradient=False)
    g = a.grad(x * x, x)
    gv = g[0] if isinstance(g, (list, tuple)) else g
    np.testing.assert_allclose(gv.numpy(), [6.0])
    with pytest.raises(NotImplementedError):
        a.forward_grad(None, None)


# -- trained seq2seq + beam decode journey ----------------------------------

def test_journey_lm_beam_decode_reproduces_pattern():
    """Train a GRU LM on a fixed token cycle, then BeamSearchDecoder must
    reproduce the cycle from the start token."""
    import paddle_tpu.nn as nn
    rs = np.random.RandomState(0)
    V, H = 6, 32
    pattern = [0, 2, 4, 1, 3, 5]       # 0 -> 2 -> 4 -> 1 -> 3 -> 5(end)
    nxt = {pattern[i]: pattern[i + 1] for i in range(len(pattern) - 1)}

    emb = nn.Embedding(V, H)
    cell = nn.GRUCell(H, H)
    head = nn.Linear(H, V)
    params = (list(emb.parameters()) + list(cell.parameters())
              + list(head.parameters()))
    opt = paddle.optimizer.Adam(0.01, parameters=params)

    xs = np.array([pattern[:-1]], np.int64)     # (1, 5)
    ys = np.array([pattern[1:]], np.int64)
    for step in range(150):
        h = paddle.to_tensor(np.zeros((1, H), np.float32))
        loss = paddle.to_tensor(0.0)
        for t in range(xs.shape[1]):
            e = emb(paddle.to_tensor(xs[:, t]))
            out, h = cell(e, h)
            logits = head(out)
            loss = loss + nn.functional.cross_entropy(
                logits, paddle.to_tensor(ys[:, t]))
        loss.backward()
        opt.step()
        opt.clear_grad()
    assert float(loss) < 0.1

    class _DecCell:
        def __call__(self, tok, h):
            out, h2 = cell(emb(tok), h)
            return head(out), h2

    dec = nn.BeamSearchDecoder(_DecCell(), start_token=0, end_token=5,
                               beam_size=2)
    init_h = paddle.to_tensor(np.zeros((1, H), np.float32))
    ids, fstate = nn.dynamic_decode(dec, inits=init_h, max_step_num=10)
    top = ids.numpy()[0, :, 0].tolist()
    assert top[:5] == pattern[1:], f"decoded {top}"


# -- review-fix regressions (r4d review) ------------------------------------

def test_bilinear_fills_all_channel_pairs():
    k = paddle.nn.initializer.Bilinear()((2, 1, 4, 4), "float32")
    arr = np.asarray(k)
    assert arr[1, 0].sum() > 0          # every out channel upsamples
    np.testing.assert_allclose(arr[0, 0], arr[1, 0])


def test_get_file_shard_uses_role_maker():
    from paddle_tpu.distributed.fleet.fleet import _FLEET
    rm = fleet.UserDefinedRoleMaker(current_id=1, role=fleet.Role.WORKER,
                                    worker_num=2)
    prev = _FLEET.get("role_maker")
    _FLEET["role_maker"] = rm
    try:
        got = fleet.UtilBase().get_file_shard(["a", "b", "c", "d", "e"])
        assert got == ["d", "e"]
    finally:
        _FLEET["role_maker"] = prev


def test_weight_quantize_group_size_rejected():
    w = paddle.to_tensor(np.ones((8, 4), np.float32))
    with pytest.raises(NotImplementedError):
        paddle.nn.quant.weight_quantize(w, group_size=128)
    q, s = paddle.nn.quant.weight_quantize(w)
    with pytest.raises(NotImplementedError):
        paddle.nn.quant.weight_only_linear(
            paddle.to_tensor(np.ones((2, 8), np.float32)), q,
            weight_scale=s, group_size=128)


def test_printoptions_sci_precision():
    paddle.set_printoptions(precision=2, sci_mode=True)
    try:
        r = repr(paddle.to_tensor([1.23456]))
        assert "1.23e+00" in r
    finally:
        paddle.set_printoptions(precision=8, sci_mode=False)


def test_localfs_touch_exist_ok(tmp_path):
    fs = fleet.utils.LocalFS()
    f = str(tmp_path / "m")
    fs.touch(f)
    with pytest.raises(FileExistsError):
        fs.touch(f, exist_ok=False)


def test_current_stream_singleton():
    cuda = paddle.device.cuda
    assert cuda.current_stream() is cuda.current_stream()


def test_save_inference_model_unknown_feed_raises(tmp_path):
    import paddle_tpu.static as static
    paddle.enable_static()
    try:
        main = static.Program()
        with static.program_guard(main):
            x = static.data("image", [None, 4], "float32")
            out = paddle.tanh(x)
        with pytest.raises(KeyError, match="imge"):
            fleet.fleet.save_inference_model(
                None, str(tmp_path), ["imge"], [out], main_program=main)
    finally:
        paddle.disable_static()


def test_scalar_operands_stay_weakly_typed():
    # Python scalars must not upcast tensor dtypes (jnp weak typing) —
    # previously ensure_tensor(2.0) made an f32 device array which
    # promoted bf16 tensors to f32
    x = paddle.to_tensor(np.ones(4, np.float32)).astype("bfloat16")
    assert "bfloat16" in str((x * 2.0).dtype)
    assert "bfloat16" in str((x ** 2).dtype)
    assert "bfloat16" in str((2.0 - x).dtype)
    # gradients unchanged
    a = paddle.to_tensor([2.0], stop_gradient=False)
    ((a ** 2) + 3.0 * a - 1.0 / a).sum().backward()
    np.testing.assert_allclose(a.grad.numpy(), [2 * 2.0 + 3.0 + 0.25])


def test_scalar_scalar_binary_still_works():
    # both operands scalar -> falls through to tensor path
    out = paddle.add(1.0, 2.0)
    assert float(out) == 3.0


# -- second review round fixes ----------------------------------------------

def test_role_maker_rejects_unlisted_server(monkeypatch):
    monkeypatch.setenv("TRAINING_ROLE", "PSERVER")
    monkeypatch.setenv("PADDLE_PSERVERS_IP_PORT_LIST", "10.0.0.1:7000")
    monkeypatch.setenv("POD_IP", "10.0.0.9")
    monkeypatch.setenv("PADDLE_PORT", "7000")
    with pytest.raises(ValueError, match="misconfigured"):
        fleet.PaddleCloudRoleMaker(is_collective=False)


def test_run_server_without_endpoints_raises():
    from paddle_tpu.distributed.fleet.fleet import _FLEET
    prev_rm = _FLEET.get("role_maker")
    prev_srv = _FLEET.pop("ps_server", None)
    _FLEET["role_maker"] = None
    try:
        with pytest.raises(RuntimeError, match="endpoints"):
            fleet.fleet.run_server()
    finally:
        _FLEET["role_maker"] = prev_rm
        if prev_srv is not None:
            _FLEET["ps_server"] = prev_srv


def test_localfs_mv_missing_src_and_dir_copy(tmp_path):
    fs = fleet.utils.LocalFS()
    with pytest.raises(FileNotFoundError):
        fs.mv(str(tmp_path / "nope"), str(tmp_path / "x"))
    d = tmp_path / "src_dir"
    d.mkdir()
    (d / "f.txt").write_text("hi")
    fs.upload(str(d), str(tmp_path / "dst_dir"))
    assert (tmp_path / "dst_dir" / "f.txt").read_text() == "hi"


def test_profiler_summary_accepts_views():
    import paddle_tpu.profiler as profiler
    p = profiler.Profiler()
    p.start()
    p.stop()
    out = p.summary(views=[profiler.SummaryView.KernelView])
    assert "Summary" in out


def test_mixed_precision_sidecar_roundtrip(tmp_path):
    import paddle_tpu.inference as inf
    src = tmp_path / "m.pdmodel"
    src.write_bytes(b"x")
    dst = tmp_path / "out" / "m.pdmodel"
    inf.convert_to_mixed_precision(str(src), None, str(dst), None,
                                   mixed_precision="bfloat16")
    cfg = inf.Config(str(dst))
    assert cfg._precision == "bfloat16"
    with pytest.raises(ValueError):
        inf.convert_to_mixed_precision(str(src), None, None, None)


def test_journey_fleet_ps_ctr_worker():
    """PS-mode CTR journey through the NEW fleet facade: server from a
    role maker, worker connects via fleet.init_worker, sparse embedding
    pulled/pushed each step, logistic loss falls."""
    from paddle_tpu.distributed.fleet.fleet import _FLEET
    from paddle_tpu.distributed.ps import PSServer

    # server side (in-process daemon): bind an ephemeral port first,
    # then hand its endpoint to the worker's role maker
    server = PSServer(port=0)
    server.create_sparse_table("emb", 8, rule="sgd", lr=0.5)
    endpoint = f"127.0.0.1:{server.port}"

    rm = fleet.UserDefinedRoleMaker(
        current_id=0, role=fleet.Role.WORKER, worker_num=1,
        server_endpoints=[endpoint])
    prev = _FLEET.get("role_maker")
    _FLEET["role_maker"] = rm
    try:
        client = fleet.fleet.init_worker()
        assert client is not None
        rs = np.random.RandomState(0)
        w_dense = np.zeros(8, np.float32)
        ids = np.arange(16)
        labels = (ids % 2).astype(np.float32)    # even ids -> 0, odd -> 1
        losses = []
        for step in range(60):
            emb = np.asarray(client.pull_sparse("emb", ids))  # (16, 8)
            logits = emb @ w_dense
            p = 1.0 / (1.0 + np.exp(-logits))
            losses.append(float(np.mean(
                -(labels * np.log(p + 1e-8)
                  + (1 - labels) * np.log(1 - p + 1e-8)))))
            dlogits = (p - labels) / len(ids)
            client.push_sparse("emb", ids, np.outer(dlogits, w_dense))
            w_dense -= 0.5 * emb.T @ dlogits
        assert losses[-1] < losses[0] * 0.7, (losses[0], losses[-1])
        fleet.fleet.stop_worker()
    finally:
        _FLEET["role_maker"] = prev


def test_launch_ps_mode_end_to_end(tmp_path):
    """fleetrun --run_mode ps: spawn 1 server + 2 trainers; each trainer
    pushes its rank-scaled gradient to a shared PS dense table; trainer 0
    verifies the accumulated value and writes a marker file."""
    import subprocess
    import sys
    import textwrap
    script = tmp_path / "ps_job.py"
    script.write_text(textwrap.dedent("""
        import os, time, json
        import numpy as np
        import paddle_tpu.distributed.fleet as fleet

        role = os.environ["TRAINING_ROLE"]
        if role == "PSERVER":
            fleet.init(is_collective=False)
            srv = fleet.fleet.init_server()
            srv.create_dense_table("w", [4], rule="sgd", lr=1.0)
            fleet.fleet.run_server()
        else:
            fleet.init(is_collective=False)
            tid = int(os.environ["PADDLE_TRAINER_ID"])
            client = None
            deadline = time.time() + 120   # server jax import can be
            while time.time() < deadline:  # slow on a contended core
                try:
                    client = fleet.fleet.init_worker()
                    client.pull_dense("w")
                    break
                except Exception:
                    client = None
                    time.sleep(0.5)
            assert client is not None, "could not reach PS server"
            g = np.full(4, float(tid + 1), np.float32)
            client.push_dense("w", g)
            time.sleep(1.0)          # let both pushes land
            if tid == 0:
                w = np.asarray(client.pull_dense("w")).reshape(-1)
                out = os.environ["PS_TEST_OUT"]
                with open(out, "w") as f:
                    json.dump({"w": w.tolist()}, f)
            fleet.fleet.stop_worker()
    """))
    import os
    out_file = tmp_path / "result.json"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PS_TEST_OUT"] = str(out_file)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    # earlier suite tests may have leaked collective PADDLE_* vars into
    # this process; the launcher scrubs too, but keep the test hermetic
    for stale in list(env):
        if stale.startswith("PADDLE_") or stale == "TRAINING_ROLE":
            env.pop(stale)
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--run_mode", "ps", "--server_num", "1", "--trainer_num", "2",
         "--log_dir", str(tmp_path / "log"), str(script)],
        env=env, timeout=300, capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    import json
    w = json.load(open(out_file))["w"]
    # sgd lr=1.0: w = -(1+2) after both trainers pushed
    np.testing.assert_allclose(w, [-3.0] * 4)


def test_convert_to_mixed_precision_casts_params(tmp_path):
    """Real jit.save artifact: converted params payload is bf16 on disk,
    and jit.load casts back to the exported program dtypes so outputs
    still match."""
    import pickle
    import paddle_tpu.jit as jit
    import paddle_tpu.inference as inf
    import paddle_tpu.nn as nn
    from paddle_tpu.static import InputSpec

    net = nn.Linear(4, 2)
    x = np.random.RandomState(0).randn(3, 4).astype(np.float32)
    want = net(paddle.to_tensor(x)).numpy()
    src = tmp_path / "m"
    jit.save(net, str(src), input_spec=[InputSpec([None, 4], "float32")])
    dst = tmp_path / "out" / "m"
    (tmp_path / "out").mkdir()
    inf.convert_to_mixed_precision(
        str(src) + ".pdmodel", str(src) + ".pdiparams",
        str(dst) + ".pdmodel", str(dst) + ".pdiparams",
        mixed_precision="bfloat16")
    with open(str(dst) + ".pdiparams", "rb") as f:
        meta = pickle.load(f)
    stored = {str(np.asarray(v).dtype) for v in meta["params"].values()}
    assert stored == {"bfloat16"}
    loaded = jit.load(str(dst))
    got = loaded(paddle.to_tensor(x)).numpy()
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_cuda_stream_guard_sets_current():
    cuda = paddle.device.cuda
    import paddle_tpu.device as device
    s = device.Stream()
    with cuda.stream_guard(s):
        assert device.current_stream() is s
    assert device.current_stream() is not s


def test_fleet_util_singleton():
    assert fleet.fleet.util is fleet.fleet.util
    assert fleet.fleet.util is fleet.util


def test_jit_save_polymorphic_batch(tmp_path):
    """None dims export symbolically: one artifact serves every batch
    size, and multi-input models share the batch symbol."""
    import paddle_tpu.jit as jit
    import paddle_tpu.nn as nn
    from paddle_tpu.static import InputSpec

    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    jit.save(net, str(tmp_path / "m"),
             input_spec=[InputSpec([None, 4], "float32")])
    loaded = jit.load(str(tmp_path / "m"))
    for B in (1, 3, 17):
        x = np.random.RandomState(B).randn(B, 4).astype(np.float32)
        np.testing.assert_allclose(
            loaded(paddle.to_tensor(x)).numpy(),
            net(paddle.to_tensor(x)).numpy(), rtol=1e-5, atol=1e-5)

    class TwoIn(nn.Layer):
        def __init__(self):
            super().__init__()
            self.l = nn.Linear(4, 2)

        def forward(self, a, b):
            return self.l(a + b)

    net2 = TwoIn()
    # r5 (ADVICE r4 #1): leading None dims are independent per input by
    # default; a model that COMBINES inputs along batch ties them
    # explicitly
    jit.save(net2, str(tmp_path / "m2"), tie_batch_dims=True,
             input_spec=[InputSpec([None, 4], "float32"),
                         InputSpec([None, 4], "float32")])
    loaded2 = jit.load(str(tmp_path / "m2"))
    a = np.ones((5, 4), np.float32)
    np.testing.assert_allclose(
        loaded2(paddle.to_tensor(a), paddle.to_tensor(a)).numpy(),
        net2(paddle.to_tensor(a), paddle.to_tensor(a)).numpy(),
        rtol=1e-5, atol=1e-5)
