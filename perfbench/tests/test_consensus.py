"""The consensus cell at its rehearsal size on the CPU (4 virtual
devices): a sound run is correct, and the comparison that decides
``correct`` fails when the mesh path is broken underneath.  Each fault
is planted in the program:

- the channel-averaged residual written into every channel (the fault
  the band pipeline once had): ``resid_gap``;
- a z-step in which each chip solves Z from its own bands, nothing
  exchanged: ``z_gap``;
- the mesh ADMM returns its warm start: ``fit_gain`` reads 1;

and the control, the reference in bfloat16 in the program's place,
fails ``resid_gap``.  Run this file in a process of its own, or first:
the rehearsal needs JAX started with four virtual devices.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

ARGS = ["--workload", "consensus.c10x32.t10", "--seconds", "3",
        "--trace", "0", "--rehearse"]


def drive(runmod, seed):
    rc = runmod.main(ARGS + ["--seed", str(seed)])
    res = runmod.LAST_RESULT
    assert res is not None
    print(f"seed {seed}: correct={res['correct']} {res['checks']}")
    return rc, res


def failed_by(res, name):
    c = res["checks"][name]
    return c["value"] > c["limit"]


def test_sound_run_is_correct(runmod):
    rc, res = drive(runmod, 4200000001)
    assert rc == 0 and res["correct"] and res["failed"] == 0
    assert res["checks"]["window_compiles"]["value"] == 0


def test_channel_averaged_residual(runmod, monkeypatch):
    from sagecal_tpu.apps import distributed

    orig = distributed.make_band_residual_fn

    def averaged(*a, **k):
        fn = orig(*a, **k)

        def res(*args):
            r = fn(*args)
            return r.mean(axis=1, keepdims=True) + 0 * r

        return res

    monkeypatch.setattr(distributed, "make_band_residual_fn", averaged)
    rc, res = drive(runmod, 4200000002)
    assert rc == 1 and not res["correct"]
    assert failed_by(res, "resid_gap")


def test_unexchanged_z(runmod, monkeypatch):
    """Each chip solves Z from its own bands; nothing crosses the mesh,
    and the Z written is the first chip's."""
    import jax

    from sagecal_tpu.parallel import consensus, mesh

    def local_only(Yhat_flat, rho, B_g, axis_name, federated_alpha=None,
                   z_extra=None, weights=None):
        z = jax.numpy.sum(
            jax.vmap(consensus.accumulate_z_term)(B_g, Yhat_flat), axis=0)
        P_loc = jax.numpy.einsum("gm,gp,gq->mpq", rho, B_g, B_g)
        return consensus.update_global_z(z, jax.numpy.linalg.pinv(P_loc))

    shard_map = jax.shard_map

    def unchecked(f, **kw):
        # a Z that differs from chip to chip cannot be typed replicated
        return shard_map(f, **{**kw, "check_vma": False})

    monkeypatch.setattr(mesh, "_zstep_grouped", local_only)
    monkeypatch.setattr(jax, "shard_map", unchecked)
    rc, res = drive(runmod, 4200000003)
    assert rc == 1 and not res["correct"]
    assert failed_by(res, "z_gap")


def test_returned_start(runmod, monkeypatch):
    from sagecal_tpu.apps import distributed

    orig = distributed.make_admm_mesh_fn

    def unchanged(*a, **k):
        fn = orig(*a, **k)

        def call(data, cdata, p0, rho, B):
            return fn(data, cdata, p0, rho, B)._replace(p=p0)

        return call

    monkeypatch.setattr(distributed, "make_admm_mesh_fn", unchanged)
    rc, res = drive(runmod, 4200000004)
    assert rc == 1 and not res["correct"]
    assert res["checks"]["fit_gain"]["value"] == pytest.approx(1.0)
    assert failed_by(res, "fit_gain")


def test_reference_bf16_is_not_correct(runmod):
    """The control: the plain reference in bfloat16, the precision below
    the configuration's float32, in the program's place for the
    residuals."""
    rc = runmod.main(ARGS + ["--seed", "4200000005",
                             "--variant", "reference_bf16"])
    res = runmod.LAST_RESULT
    assert rc == 1 and not res["correct"]
    assert failed_by(res, "resid_gap")
