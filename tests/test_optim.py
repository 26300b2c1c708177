"""Optimizer engine tests: schedule values, update-rule numerics vs
torch.optim (the golden-oracle pattern of TEST/torch), triggers, and the
LeNet end-to-end slice (mirrors models/lenet/Train.scala +
RefLocalOptimizer-style convergence checks)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
import bigdl_tpu.optim as optim
from bigdl_tpu.dataset import DataSet
from bigdl_tpu.dataset.mnist import load_mnist
from bigdl_tpu.models import LeNet5


# ---------------------------------------------------------------- schedules
def test_poly_schedule():
    s = optim.Poly(0.5, 100)
    assert s.rate(0) == 1.0
    assert s.rate(100) == 0.0
    assert abs(s.rate(50) - math.sqrt(0.5)) < 1e-9


def test_step_multistep():
    assert optim.Step(10, 0.5).rate(25) == 0.25
    ms = optim.MultiStep([10, 20], 0.1)
    assert ms.rate(5) == 1.0 and abs(ms.rate(15) - 0.1) < 1e-12
    assert abs(ms.rate(25) - 0.01) < 1e-12


def test_sequential_warmup_poly():
    warm = optim.Warmup(0.1)
    warm.base_lr = 1.0
    seq = optim.SequentialSchedule().add(warm, 5).add(optim.Poly(1.0, 10), 10)
    assert seq.rate(0) == 1.0
    assert abs(seq.rate(4) - 1.4) < 1e-9
    assert abs(seq.rate(5) - 1.0) < 1e-9  # poly step 0
    assert abs(seq.rate(10) - 0.5) < 1e-9  # poly step 5


def test_plateau():
    p = optim.Plateau(factor=0.5, patience=2, mode="min")
    for v in [1.0, 0.9, 0.91, 0.92, 0.93]:
        p.record(v)
    assert p.rate(0) == 0.5


# ------------------------------------------------------- update-rule goldens
def _train_quadratic(method, steps=150):
    """Minimize ||Wx - y||^2 with the given method; return final params."""
    key = jax.random.PRNGKey(3)
    W = jax.random.normal(key, (4, 4))
    x = jnp.arange(4.0)
    y = jnp.ones(4)
    params = {"w": W}
    opt_state = method.init_state(params)

    def loss(p):
        return jnp.sum((p["w"] @ x - y) ** 2)

    for t in range(1, steps + 1):
        g = jax.grad(loss)(params)
        lr = jnp.asarray(method.learning_rate, jnp.float32)
        params, opt_state = method.update(
            g, opt_state, params, lr, jnp.asarray(t, jnp.int32)
        )
    return float(loss(params))


@pytest.mark.parametrize(
    "method,target",
    [
        (optim.SGD(1e-2, momentum=0.9), 3.0),
        (optim.Adam(5e-2), 3.0),
        (optim.Adagrad(1e-1), 3.0),
        (optim.Adadelta(epsilon=1e-4), 10.0),  # adaptive warm-up is slow by design
        (optim.RMSprop(1e-2), 3.0),
        (optim.Adamax(2e-3), 60.0),  # tiny default LR; just verify descent
        (optim.LarsSGD(1e-2, momentum=0.9, weight_decay=1e-4), 3.0),
        (optim.Ftrl(5e-2), 5.0),
    ],
)
def test_methods_reduce_loss(method, target):
    final = _train_quadratic(method)
    assert final < target, f"{type(method).__name__} did not reduce loss: {final}"


def test_sgd_matches_torch():
    torch = pytest.importorskip("torch")
    w0 = np.random.RandomState(0).randn(3, 3).astype(np.float32)
    x = np.arange(3, dtype=np.float32)

    # torch side
    tw = torch.tensor(w0, requires_grad=True)
    opt = torch.optim.SGD([tw], lr=0.1, momentum=0.9, weight_decay=1e-2)
    for _ in range(10):
        opt.zero_grad()
        loss = ((tw @ torch.tensor(x)) ** 2).sum()
        loss.backward()
        opt.step()

    # ours (pytorch's dampening default is 0; ours follows the Torch7/
    # reference convention dampening=momentum, so pass 0 explicitly)
    method = optim.SGD(0.1, momentum=0.9, dampening=0.0, weight_decay=1e-2)
    params = {"w": jnp.asarray(w0)}
    st = method.init_state(params)

    def loss_fn(p):
        return jnp.sum((p["w"] @ jnp.asarray(x)) ** 2)

    for t in range(1, 11):
        g = jax.grad(loss_fn)(params)
        params, st = method.update(
            g, st, params, jnp.asarray(0.1, jnp.float32), jnp.asarray(t, jnp.int32)
        )
    np.testing.assert_allclose(
        np.asarray(params["w"]), tw.detach().numpy(), rtol=2e-4, atol=2e-5
    )


def test_adam_matches_torch():
    torch = pytest.importorskip("torch")
    w0 = np.random.RandomState(1).randn(4).astype(np.float32)
    tw = torch.tensor(w0, requires_grad=True)
    opt = torch.optim.Adam([tw], lr=0.05)
    for _ in range(20):
        opt.zero_grad()
        ((tw**2).sum()).backward()
        opt.step()

    method = optim.Adam(0.05)
    params = {"w": jnp.asarray(w0)}
    st = method.init_state(params)
    for t in range(1, 21):
        g = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
        params, st = method.update(
            g, st, params, jnp.asarray(0.05, jnp.float32), jnp.asarray(t, jnp.int32)
        )
    np.testing.assert_allclose(
        np.asarray(params["w"]), tw.detach().numpy(), rtol=1e-4, atol=1e-5
    )


# ------------------------------------------------------------------ triggers
def test_triggers():
    t = optim.Trigger.max_epoch(3)
    assert not t({"epoch": 2}) and t({"epoch": 3})
    t = optim.Trigger.several_iteration(5)
    assert t({"neval": 10}) and not t({"neval": 11})
    combo = optim.Trigger.or_(
        optim.Trigger.max_iteration(100), optim.Trigger.min_loss(0.1)
    )
    assert combo({"neval": 100, "loss": 1.0})
    assert combo({"neval": 5, "loss": 0.01})


# ------------------------------------------------------- validation methods
def test_top1_top5():
    out = jnp.asarray([[0.1, 0.9, 0.0], [0.8, 0.1, 0.1]])
    tgt = jnp.asarray([1, 2])
    r1 = optim.Top1Accuracy()(out, tgt)
    assert r1.result() == (0.5, 2)
    r5 = optim.Top5Accuracy()(out, tgt)
    assert r5.result()[0] == 1.0


# -------------------------------------------------------------- e2e LeNet
def test_lenet_end_to_end(tmp_path):
    """The minimum end-to-end slice of SURVEY.md §7.3: LeNet on (synthetic)
    MNIST with the LocalOptimizer, validation, checkpointing."""
    x_train, y_train = load_mnist(train=True, synthetic_n=1024)
    x_val, y_val = load_mnist(train=False, synthetic_n=256)
    train_ds = DataSet.from_arrays(x_train, y_train, batch_size=128)
    val_ds = DataSet.from_arrays(x_val, y_val, batch_size=128)

    model = LeNet5(10)
    opt = (
        optim.Optimizer.apply(
            model, train_ds, nn.ClassNLLCriterion(logits=True),
            end_trigger=optim.Trigger.max_epoch(3),
        )
        .set_optim_method(optim.Adam(1e-3))
        .set_validation(
            optim.Trigger.every_epoch(), val_ds, [optim.Top1Accuracy()]
        )
        .set_checkpoint(str(tmp_path / "ckpt"), optim.Trigger.every_epoch())
    )
    trained = opt.optimize()
    results = optim.evaluate(
        trained, opt.final_params, opt.final_state, val_ds, [optim.Top1Accuracy()]
    )
    acc = results[0][1].result()[0]
    assert acc > 0.9, f"LeNet e2e accuracy too low: {acc}"
    # checkpoint was written and can be resumed from
    import os

    assert any(f.startswith("model") for f in os.listdir(tmp_path / "ckpt"))


def test_checkpoint_resume(tmp_path):
    x, y = load_mnist(train=True, synthetic_n=512)
    ds = DataSet.from_arrays(x, y, batch_size=128)
    model = LeNet5(10)
    opt = (
        optim.Optimizer.apply(
            model, ds, nn.ClassNLLCriterion(logits=True),
            end_trigger=optim.Trigger.max_epoch(1),
        )
        .set_optim_method(optim.SGD(0.05, momentum=0.9))
        .set_checkpoint(str(tmp_path / "ck"), optim.Trigger.every_epoch())
    )
    opt.optimize()

    model2 = LeNet5(10)
    opt2 = (
        optim.Optimizer.apply(
            model2, ds, nn.ClassNLLCriterion(logits=True),
            end_trigger=optim.Trigger.max_epoch(2),
        )
        .set_optim_method(optim.SGD(0.05, momentum=0.9))
        .resume_from(str(tmp_path / "ck" / "model"))
    )
    opt2.optimize()
    # resumed run continued from epoch 1 -> did exactly 1 more epoch
    assert opt2._resume_from is not None


def test_lars_matches_closed_form():
    """One and two LarsSGD steps against the documented trust-ratio
    formula (reference optim/LarsSGD.scala:17-40) computed in numpy."""
    w = np.array([[1.0, 2.0], [3.0, -1.0]], np.float32)
    g = np.array([[0.1, -0.2], [0.05, 0.3]], np.float32)
    lr, mom, wd, trust = 0.1, 0.9, 1e-3, 1.0
    m = optim.LarsSGD(lr, momentum=mom, weight_decay=wd, trust=trust)
    params = {"l": {"weight": jnp.asarray(w)}}
    st = m.init_state(params)
    grads = {"l": {"weight": jnp.asarray(g)}}

    p1, st1 = m.update(grads, st, params, jnp.asarray(lr, jnp.float32), 1)

    def expected_step(w_np, g_np, v_np):
        w_norm = np.linalg.norm(w_np)
        g_norm = np.linalg.norm(g_np)
        ratio = trust * w_norm / (g_norm + wd * w_norm + 1e-12)
        v = mom * v_np + lr * ratio * (g_np + wd * w_np)
        return w_np - v, v

    e1, v1 = expected_step(w, g, np.zeros_like(w))
    np.testing.assert_allclose(np.asarray(p1["l"]["weight"]), e1, rtol=1e-6)
    # momentum carries into step 2
    p2, _ = m.update(grads, st1, p1, jnp.asarray(lr, jnp.float32), 2)
    e2, _ = expected_step(e1, g, v1)
    np.testing.assert_allclose(np.asarray(p2["l"]["weight"]), e2, rtol=1e-5)


def test_gradient_accumulation_matches_full_batch():
    """accum_steps=k on a BN-free model must produce the same update as
    the single full-batch step (mean-of-micro-grads == full-batch grad
    for a mean-reduced criterion)."""
    import jax
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import make_train_step

    model = nn.Sequential(nn.Linear(6, 8), nn.Tanh(), nn.Linear(8, 3))
    crit = nn.ClassNLLCriterion(logits=True)
    methods = {"__all__": SGD(0.1, momentum=0.9)}

    variables = model.init(jax.random.PRNGKey(0))
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(16, 6), jnp.float32)
    t = jnp.asarray(rs.randint(0, 3, 16))
    lrs = [jnp.asarray(0.1, jnp.float32)]

    outs = {}
    for k in (1, 4):
        step = jax.jit(make_train_step(model, crit, methods,
                                       accum_steps=k))
        opt = {"__all__": methods["__all__"].init_state(
            variables["params"])}
        p, s, o, loss = step(variables["params"], variables["state"],
                             opt, jnp.asarray(0, jnp.int32),
                             jax.random.PRNGKey(1), x, t, lrs)
        outs[k] = (jax.tree_util.tree_map(np.asarray, p), float(loss))

    np.testing.assert_allclose(outs[1][1], outs[4][1], rtol=1e-5)
    for (a, b) in zip(jax.tree_util.tree_leaves(outs[1][0]),
                      jax.tree_util.tree_leaves(outs[4][0])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_gradient_accumulation_trains_end_to_end():
    """Optimizer.set_gradient_accumulation: loss falls on a learnable
    task at constant memory."""
    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset import DataSet

    rs = np.random.RandomState(1)
    x = rs.randn(128, 10).astype(np.float32)
    w = rs.randn(10, 3).astype(np.float32)
    y = (x @ w).argmax(-1)

    model = nn.Sequential(nn.Linear(10, 16), nn.ReLU(), nn.Linear(16, 3))
    opt = (optim.Optimizer.apply(
               model, DataSet.from_arrays(x, y, batch_size=32),
               nn.ClassNLLCriterion(logits=True),
               end_trigger=optim.Trigger.max_epoch(30))
           .set_optim_method(optim.SGD(0.2, momentum=0.9))
           .set_gradient_accumulation(4))
    opt.optimize()
    # evaluate the trained params directly
    res = optim.evaluate(model, opt.final_params, opt.final_state,
                         DataSet.from_arrays(x, y, batch_size=32),
                         [optim.Top1Accuracy()])
    acc = res[0][1].result()[0]
    assert acc > 0.85, acc


def test_evaluate_batch_to_device_flag(monkeypatch):
    """evaluate(batch_to_device=False) must SKIP the explicit
    host->device jnp.asarray on the batch (for datasets that already
    yield device-resident arrays) while producing identical results."""
    rs = np.random.RandomState(0)
    x = rs.randn(32, 6).astype(np.float32)
    y = rs.randint(0, 3, 32)
    model = nn.Sequential(nn.Linear(6, 8), nn.ReLU(), nn.Linear(8, 3))
    variables = model.init(jax.random.PRNGKey(0))
    ds = DataSet.from_arrays(x, y, batch_size=16)

    placed = []
    orig_asarray = jnp.asarray

    def spy(a, *args, **kwargs):
        if isinstance(a, np.ndarray) and a.shape == (16, 6):
            placed.append(a.shape)
        return orig_asarray(a, *args, **kwargs)

    monkeypatch.setattr(jnp, "asarray", spy)
    res_skip = optim.evaluate(model, variables["params"],
                              variables["state"], ds,
                              [optim.Top1Accuracy()],
                              batch_to_device=False)
    assert not placed, "batch_to_device=False still placed the batch"
    res_place = optim.evaluate(model, variables["params"],
                               variables["state"], ds,
                               [optim.Top1Accuracy()])
    assert placed, "batch_to_device=True no longer places the batch"
    monkeypatch.undo()
    assert res_skip[0][1].result() == res_place[0][1].result()


def test_lbfgs_wolfe_line_search_on_rosenbrock():
    """LBFGS + strong-Wolfe (reference optim/LineSearch.scala lswolfe)
    minimizes Rosenbrock where the fixed unit step diverges."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.optim import LBFGS

    def rosen(v):
        return (1 - v[0]) ** 2 + 100.0 * (v[1] - v[0] ** 2) ** 2

    vg = jax.jit(jax.value_and_grad(rosen))

    def feval(x):
        l, g = vg(x)
        return l, g

    x0 = jnp.asarray([-1.2, 1.0])
    m = LBFGS(max_iter=60, learning_rate=1.0, line_search="wolfe")
    x_star, losses = m.optimize(feval, x0)
    assert losses[-1] < 1e-5, losses[-1]
    np.testing.assert_allclose(np.asarray(x_star), [1.0, 1.0], atol=1e-2)

    # fixed unit step on the same problem must NOT converge (it is why
    # the line search exists)
    m2 = LBFGS(max_iter=60, learning_rate=1.0)
    _, losses2 = m2.optimize(feval, x0)
    assert not losses2[-1] < 1e-5 or not np.isfinite(losses2[-1])


def test_compute_dtype_casts_float_features_not_integer_ones():
    """set_compute_dtype(bf16) must put the whole network in bf16: the
    layers compute in their input's dtype, so an f32 image batch left
    as it is would keep every activation in f32 (on the chip: 14.4 GiB
    of temporaries for ResNet-50 at batch 256 instead of 8.5)."""
    from bigdl_tpu.optim.optimizer import make_train_step

    seen = {}

    class Probe(nn.Linear):
        def apply(self, params, state, x, training=False, rng=None):
            seen["dtype"] = x.dtype
            return super().apply(params, state, x, training=training,
                                 rng=rng)

    model = Probe(4, 3)
    var = model.init(jax.random.PRNGKey(0))
    methods = {"__all__": optim.SGD(0.1)}
    step = make_train_step(model, nn.ClassNLLCriterion(logits=True),
                           methods, compute_dtype=jnp.bfloat16)
    opt = {"__all__": methods["__all__"].init_state(var["params"])}
    x = jnp.ones((2, 4), jnp.float32)
    t = jnp.zeros((2,), jnp.int32)
    out = step(var["params"], var["state"], opt, jnp.int32(1),
               jax.random.PRNGKey(1), x, t, [jnp.float32(0.1)])
    assert seen["dtype"] == jnp.bfloat16
    assert out[0]["weight"].dtype == jnp.float32  # master weights stay
    assert np.isfinite(float(out[3]))
