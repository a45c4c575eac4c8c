"""Transform-algebra correctness.

Golden-trajectory equivalence: every chain-built registry optimizer must
reproduce the pre-refactor monolithic implementation bit-for-bit (fp32
tolerance) over 13 steps on a ring.  The reference fingerprints below were
generated by running the monolithic ``core/optim.py`` (commit a5aa3f2) on
the exact toy problem in ``_traj`` and freezing (sum, l2-norm) of the
node-stacked params after every step — frozen in this file, not on disk.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gossip, optim, topology
from repro.core import transforms as T

STEPS = 13
LR = 0.05
WD = 1e-4

# (sum(params), ||params||_2) after each of 13 steps, monolithic reference.
GOLDEN = {
    'd2': [
        (-1.657943, 4.071104),
        (-1.569763, 4.234638),
        (-1.742373, 3.844862),
        (-1.961495, 3.694109),
        (-2.344901, 3.575547),
        (-2.721284, 3.60206),
        (-2.878883, 3.624982),
        (-2.904477, 3.615456),
        (-2.992716, 3.558974),
        (-3.19345, 3.466332),
        (-3.47347, 3.356235),
        (-3.843006, 3.265447),
        (-4.320626, 3.226722),
    ],
    'd2_plus': [
        (-1.657943, 4.071104),
        (-1.569763, 4.234638),
        (-1.742373, 3.844862),
        (-1.961495, 3.694109),
        (-2.344901, 3.575547),
        (-2.721284, 3.60206),
        (-2.878883, 3.624982),
        (-2.904477, 3.615456),
        (-2.992716, 3.558974),
        (-3.19345, 3.466332),
        (-3.47347, 3.356235),
        (-3.843006, 3.265447),
        (-4.320626, 3.226722),
    ],
    'dadam': [
        (-1.655208, 4.039862),
        (-1.402301, 2.862936),
        (-1.148286, 2.352016),
        (-0.915132, 1.87856),
        (-0.707057, 1.414698),
        (-0.544874, 1.046725),
        (-0.466626, 0.875804),
        (-0.480603, 0.880719),
        (-0.524378, 0.914265),
        (-0.577796, 0.902811),
        (-0.651471, 0.853041),
        (-0.751457, 0.80615),
        (-0.822011, 0.784917),
    ],
    'dmsgd': [
        (-1.657943, 4.071104),
        (-1.572224, 3.256544),
        (-1.516985, 2.402889),
        (-1.423662, 1.761163),
        (-1.326141, 0.987112),
        (-1.215132, 0.847339),
        (-0.93424, 1.181485),
        (-0.4097, 1.364299),
        (0.024331, 1.376744),
        (0.180515, 1.409002),
        (0.255818, 1.700428),
        (0.267155, 2.089244),
        (0.401687, 2.327828),
    ],
    'dsgd': [
        (-1.657943, 4.071104),
        (-1.569763, 2.933836),
        (-1.492301, 2.545808),
        (-1.465941, 2.260479),
        (-1.523858, 2.02933),
        (-1.697827, 1.871878),
        (-1.982943, 1.797148),
        (-2.254371, 1.751555),
        (-2.396485, 1.661863),
        (-2.505621, 1.54495),
        (-2.661344, 1.427369),
        (-2.874087, 1.325834),
        (-3.116647, 1.257266),
    ],
    'dsgdm': [
        (-1.657943, 4.071104),
        (-1.572224, 2.864936),
        (-1.421687, 2.229736),
        (-1.252493, 1.522058),
        (-1.107323, 0.849632),
        (-0.959842, 0.85944),
        (-0.640139, 1.247219),
        (-0.182348, 1.438108),
        (0.036348, 1.452754),
        (0.011935, 1.524449),
        (-0.024854, 1.90003),
        (-0.083295, 2.363049),
        (-0.004121, 2.575748),
    ],
    'dsgdm_n': [
        (-1.660404, 3.962842),
        (-1.499305, 2.609551),
        (-1.284236, 1.795375),
        (-1.054344, 0.989947),
        (-0.764444, 0.528019),
        (-0.263335, 0.724916),
        (0.193867, 0.832883),
        (0.375904, 0.67999),
        (0.472743, 0.420212),
        (0.464115, 0.277501),
        (0.179646, 0.202541),
        (-0.106628, 0.150216),
        (-0.152408, 0.093171),
    ],
    'dsgdm_n_sync': [
        (-1.660404, 3.962842),
        (-1.499306, 2.601731),
        (-1.27848, 1.796923),
        (-1.045538, 0.987228),
        (-0.759051, 0.522578),
        (-0.262839, 0.720306),
        (0.194979, 0.828745),
        (0.377919, 0.676044),
        (0.475048, 0.41707),
        (0.464743, 0.275736),
        (0.178225, 0.200274),
        (-0.107941, 0.147434),
        (-0.152629, 0.089296),
    ],
    'dsgdm_n_sync_global': [
        (-1.660404, 3.962842),
        (-1.499306, 2.608636),
        (-1.277648, 1.800984),
        (-1.044504, 0.989501),
        (-0.758669, 0.522903),
        (-0.263704, 0.719511),
        (0.194442, 0.828334),
        (0.377482, 0.676064),
        (0.474502, 0.417204),
        (0.464507, 0.275761),
        (0.178615, 0.200264),
        (-0.107692, 0.1474),
        (-0.152543, 0.089314),
    ],
    'dsgdm_sync': [
        (-1.657943, 4.071104),
        (-1.572225, 2.855937),
        (-1.417673, 2.231348),
        (-1.24313, 1.519071),
        (-1.095317, 0.844857),
        (-0.95066, 0.855941),
        (-0.636651, 1.244819),
        (-0.1804, 1.434098),
        (0.04109, 1.447321),
        (0.021634, 1.518812),
        (-0.009056, 1.893769),
        (-0.06626, 2.355336),
        (0.004512, 2.570394),
    ],
    'gt': [
        (-1.657943, 4.071104),
        (-1.569763, 2.927001),
        (-1.48772, 2.545102),
        (-1.458386, 2.261788),
        (-1.516276, 2.030465),
        (-1.690941, 1.873033),
        (-1.976262, 1.798507),
        (-2.246845, 1.752885),
        (-2.387657, 1.663072),
        (-2.49576, 1.546021),
        (-2.651714, 1.428134),
        (-2.867082, 1.326201),
        (-3.113293, 1.257387),
    ],
    'gt_dsgdm_n': [
        (-1.660404, 3.962842),
        (-1.499306, 2.595196),
        (-1.270932, 1.799241),
        (-1.033522, 0.993579),
        (-0.749684, 0.527216),
        (-0.260477, 0.720417),
        (0.194031, 0.828155),
        (0.374588, 0.675826),
        (0.470826, 0.417517),
        (0.462504, 0.276364),
        (0.179128, 0.200579),
        (-0.106641, 0.147629),
        (-0.151917, 0.089813),
    ],
    'qg_dadam': [
        (-1.655208, 4.039862),
        (-1.433984, 2.869604),
        (-1.346171, 2.436807),
        (-1.359969, 2.090331),
        (-1.404775, 1.761181),
        (-1.445214, 1.49937),
        (-1.656746, 1.361116),
        (-2.127502, 1.356066),
        (-2.479663, 1.425726),
        (-2.70562, 1.458936),
        (-2.727268, 1.437086),
        (-2.708167, 1.390055),
        (-2.788325, 1.355739),
    ],
    'qg_dsgdm': [
        (-1.657943, 4.071104),
        (-1.570009, 2.957826),
        (-1.499175, 2.504522),
        (-1.457288, 2.190728),
        (-1.493287, 1.889416),
        (-1.639661, 1.650472),
        (-1.923396, 1.500502),
        (-2.285529, 1.438293),
        (-2.546654, 1.393579),
        (-2.614425, 1.3336),
        (-2.540298, 1.260837),
        (-2.387597, 1.170338),
        (-2.19123, 1.066169),
    ],
    'qg_dsgdm_n': [
        (-1.660404, 3.962842),
        (-1.497511, 2.685328),
        (-1.35163, 2.006473),
        (-1.226553, 1.456457),
        (-1.151827, 0.972335),
        (-1.053737, 0.645883),
        (-0.807053, 0.449504),
        (-0.380091, 0.290345),
        (-0.004028, 0.232825),
        (0.102349, 0.210425),
        (0.105528, 0.175359),
        (0.086674, 0.143464),
        (0.067959, 0.118843),
    ],
    'qhm': [
        (-0.899377, 3.680565),
        (-0.836531, 3.569745),
        (-0.870069, 3.513228),
        (-0.993036, 3.441321),
        (-1.114584, 3.413608),
        (-1.248873, 3.426175),
        (-1.469944, 3.395547),
        (-1.588431, 3.314472),
        (-1.605203, 3.242688),
        (-1.669588, 3.179455),
        (-1.796137, 3.128574),
        (-1.742759, 3.08581),
        (-1.546078, 3.031337),
    ],
    'slowmo': [
        (-1.660404, 3.962842),
        (-1.499305, 2.609551),
        (-1.284236, 1.795375),
        (-1.054344, 0.989947),
        (-0.764444, 0.528019),
        (-0.263335, 0.724916),
        (0.193867, 0.832883),
        (0.375904, 0.67999),
        (0.472743, 0.420212),
        (0.464115, 0.277501),
        (0.179646, 0.202541),
        (-0.106628, 0.148096),
        (0.011525, 0.027722),
    ],
    'qg_dsgdm_tau4': [
        (-1.657943, 4.071104),
        (-1.569763, 2.933836),
        (-1.492301, 2.545808),
        (-1.465941, 2.260479),
        (-1.521485, 2.006414),
        (-1.695358, 1.829085),
        (-1.987361, 1.738727),
        (-2.282495, 1.683546),
        (-2.479571, 1.584593),
        (-2.608597, 1.45314),
        (-2.682131, 1.318401),
        (-2.61693, 1.193113),
        (-2.367629, 1.070663),
    ],
    'dmsgd_opt1': [
        (-1.657943, 4.071104),
        (-1.572224, 3.256544),
        (-1.516985, 2.621011),
        (-1.365327, 2.019848),
        (-1.182177, 1.061525),
        (-1.069188, 0.982308),
        (-0.950445, 1.270099),
        (-0.748125, 1.354455),
        (-0.400817, 1.3422),
        (-0.186244, 1.365135),
        (-0.10991, 1.601666),
        (-0.217536, 1.944724),
        (-0.242533, 2.114242),
    ],
}


def toy_params(n, seed=0):
    # the goldens were recorded with the non-partitionable threefry draw;
    # pin it so they hold whatever the process-wide PRNG default is
    k = jax.random.PRNGKey(seed)
    with jax.threefry_partitionable(False):
        return {
            "w": jax.random.normal(k, (n, 5, 3)),
            "b": jax.random.normal(jax.random.fold_in(k, 1), (n, 3)),
        }


def toy_grad(params, t):
    return jax.tree.map(lambda x: jnp.sin(x * (t + 1)), params)


def fingerprint(p):
    s = sum(float(jnp.sum(l)) for l in jax.tree.leaves(p))
    n = sum(float(jnp.sum(l.astype(jnp.float32) ** 2))
            for l in jax.tree.leaves(p)) ** 0.5
    return (s, n)


def _traj(opt, n, steps=STEPS):
    p = toy_params(n)
    s = opt.init(p)
    w = jnp.asarray(topology.ring(n).w(), jnp.float32) if n > 1 else jnp.eye(1)
    out = []
    for t in range(steps):
        g = toy_grad(p, t)
        p, s = opt.step(p, g, s, w=w, lr=LR, t=t)
        out.append(fingerprint(p))
    return out, p, s


def _assert_matches_golden(name, opt, n):
    traj, p, s = _traj(opt, n)
    ref = GOLDEN[name]
    got_s = np.array([a for a, _ in traj])
    got_n = np.array([b for _, b in traj])
    np.testing.assert_allclose(got_s, np.array([a for a, _ in ref]),
                               rtol=1e-4, atol=2e-5, err_msg=name)
    np.testing.assert_allclose(got_n, np.array([b for _, b in ref]),
                               rtol=1e-4, atol=2e-5, err_msg=name)
    assert all(bool(jnp.all(jnp.isfinite(l))) for l in jax.tree.leaves(s))


# --- golden-trajectory equivalence for every pre-refactor registry entry ----

@pytest.mark.parametrize("name", sorted(k for k in GOLDEN
                                        if k not in ("qg_dsgdm_tau4",
                                                     "dmsgd_opt1")))
def test_chain_matches_monolith_golden(name):
    n = 1 if name == "qhm" else 4
    opt = optim.make_optimizer(name, lr=LR, weight_decay=WD)
    _assert_matches_golden(name, opt, n)


def test_chain_matches_monolith_golden_qg_tau4():
    opt = optim.QGDSGDm(lr=LR, weight_decay=WD, tau=4)
    _assert_matches_golden("qg_dsgdm_tau4", opt, 4)


def test_chain_matches_monolith_golden_dmsgd_opt1():
    opt = optim.DMSGD(lr=LR, weight_decay=WD, option=1)
    _assert_matches_golden("dmsgd_opt1", opt, 4)


# --- the documented-but-previously-missing registry entry -------------------

def test_qg_dsgdm_tau_registry_entry():
    opt = optim.make_optimizer("qg_dsgdm_tau", lr=LR)
    assert isinstance(opt, optim.QGDSGDm) and opt.tau == 4
    assert optim.make_optimizer("qg_dsgdm_tau", lr=LR, tau=2).tau == 2
    # matches the class-built tau=4 trajectory
    _assert_matches_golden(
        "qg_dsgdm_tau4",
        optim.make_optimizer("qg_dsgdm_tau", lr=LR, weight_decay=WD), 4)


def test_qg_tau_carries_buffer_between_refreshes():
    """tau=4: m_hat must stay EXACTLY constant on non-refresh steps and
    change on steps with (t+1) % 4 == 0."""
    n = 4
    opt = optim.make_optimizer("qg_dsgdm_tau", lr=LR, tau=4)
    w = jnp.asarray(topology.ring(n).w(), jnp.float32)
    p = toy_params(n)
    s = opt.init(p)
    bufs = []
    for t in range(9):
        g = toy_grad(p, t)
        p, s = opt.step(p, g, s, w=w, lr=LR, t=t)
        bufs.append(np.asarray(s["qg_buffer"]["m_hat"]["w"]))
    for t in range(1, 9):
        if t % 4 == 0:  # refresh landed at t-1 where (t-1+1) % 4 == 0
            assert float(np.abs(bufs[t - 1] - bufs[t - 2]).max()) > 1e-7
        elif t % 4 != 0 and (t % 4) != 1:
            np.testing.assert_array_equal(bufs[t - 1], bufs[t - 2])
    # ... and the buffer is actually used: differs from tau=1 params
    p1, _, _ = _traj(optim.make_optimizer("qg_dsgdm", lr=LR), n)
    p4, _, _ = _traj(opt, n)
    assert abs(p1[-1][0] - p4[-1][0]) > 1e-6


# --- the new tracking-family entries ----------------------------------------

def test_mt_dsgdm_is_grad_track_plus_heavyball():
    """Momentum Tracking == gradient tracking followed by heavyball, i.e.
    momentum applied to the tracked gradient."""
    n = 4
    opt = optim.make_optimizer("mt_dsgdm", lr=LR)
    names = [s.name for s in opt._stages()]
    assert names == ["weight_decay", "grad_track", "heavyball", "gossip_mix"]
    traj, p, _ = _traj(opt, n)
    assert all(np.isfinite(a) and np.isfinite(b) for a, b in traj)
    # differs from plain gt (no momentum) and from nesterov gt
    t_gt, _, _ = _traj(optim.make_optimizer("gt", lr=LR), n)
    t_n, _, _ = _traj(optim.make_optimizer("gt_dsgdm_n", lr=LR), n)
    assert abs(traj[-1][0] - t_gt[-1][0]) > 1e-6
    assert abs(traj[-1][0] - t_n[-1][0]) > 1e-6


def test_gut_tracks_momentum_update():
    """GUT = the same stages as MT in the opposite order; the tracker state
    must hold the momentum update, not the raw gradient."""
    n = 4
    opt = optim.make_optimizer("gut", lr=LR)
    names = [s.name for s in opt._stages()]
    assert names == ["weight_decay", "heavyball", "grad_track", "gossip_mix"]
    w = jnp.asarray(topology.ring(n).w(), jnp.float32)
    p = toy_params(n)
    s = opt.init(p)
    g = toy_grad(p, 0)
    p1, s1 = opt.step(p, g, s, w=w, lr=LR, t=0)
    # first step: tracker y == u == heavyball(0 momentum buffer) == g
    np.testing.assert_allclose(np.asarray(s1["grad_track"]["y"]["w"]),
                               np.asarray(g["w"]), atol=1e-6)
    g2 = toy_grad(p1, 1)
    p2, s2 = opt.step(p1, g2, s1, w=w, lr=LR, t=1)
    # second step: prev_u is the momentum update beta*m + g2, NOT g2
    expect_u = 0.9 * np.asarray(s1["heavyball"]["m"]["w"]) + np.asarray(
        g2["w"])
    np.testing.assert_allclose(np.asarray(s2["grad_track"]["prev_u"]["w"]),
                               expect_u, rtol=1e-5)
    traj, _, _ = _traj(opt, n)
    assert all(np.isfinite(a) and np.isfinite(b) for a, b in traj)


@pytest.mark.parametrize("name", ["mt_dsgdm", "gut"])
def test_new_entries_compose_with_compressed_gossip(name):
    """Tracking-family chains make 2 mix calls (tracker site first), so they
    pick up 2 CHOCO replica sites through the mix_fn hook unchanged."""
    from repro.comm import CompressedGossip, count_mix_sites, make_compressor
    p = {"w": jnp.ones((4, 6, 2)), "b": jnp.ones((4, 2))}
    w = topology.ring(4).w()
    opt = optim.make_optimizer(name, lr=LR)
    assert count_mix_sites(opt, p, w) == 2
    comm = CompressedGossip(compressor=make_compressor("topk:0.25"))
    sites = comm.init_state(opt, p, w)
    assert len(sites) == 2
    assert float(jnp.abs(sites[0]["x_hat"]["w"]).max()) == 0.0  # tracker site
    np.testing.assert_allclose(np.asarray(sites[1]["x_hat"]["w"]),
                               np.asarray(p["w"]))              # params site


# --- algebra contracts ------------------------------------------------------

def test_chain_rejects_duplicate_names():
    with pytest.raises(ValueError):
        T.chain(T.gossip_mix(), T.gossip_mix())


def test_stateless_stages_have_no_state_entry():
    opt = optim.make_optimizer("qg_dsgdm_n", lr=LR)
    state = opt.init(toy_params(4))
    assert set(state) == {"qg_buffer"}  # wd/heavyball(seeded)/mix: stateless


def test_stage_sees_pre_and_post_mix_params():
    """A probe stage chained after gossip_mix observes both views, and the
    difference is exactly the gossip displacement."""
    seen = {}

    def probe_apply(ctx, sv, states):
        seen["pre"] = sv.params_pre_mix
        seen["post"] = sv.params_post_mix
        seen["lr"] = ctx.lr
        seen["t"] = ctx.t
        return sv, states

    probe = T.Stage("probe", lambda p: None, probe_apply)

    class Probed(optim.DecentralizedOptimizer):
        def _stages(self):
            return T.chain(T.weight_decay(0.0), T.gossip_mix(), probe)

    n = 4
    w = jnp.asarray(topology.ring(n).w(), jnp.float32)
    p = toy_params(n)
    opt = Probed(lr=LR)
    g = toy_grad(p, 0)
    out, _ = opt.step(p, g, opt.init(p), w=w, lr=LR, t=7)
    assert seen["t"] == 7 and float(seen["lr"]) == LR
    np.testing.assert_allclose(np.asarray(seen["pre"]["w"]),
                               np.asarray(p["w"]))
    half = jax.tree.map(lambda x, gg: x - LR * gg, p, g)
    np.testing.assert_allclose(np.asarray(seen["post"]["w"]),
                               np.asarray(gossip.mix_dense(w, half)["w"]),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out["w"]),
                               np.asarray(seen["post"]["w"]))


def test_mix_fn_replace_reaches_chain():
    """dataclasses.replace(opt, mix_fn=...) — the CHOCO/trainer idiom — must
    route every mix site of the rebuilt chain through the new hook."""
    import dataclasses as dc
    calls = []

    def counting_mix(w, tree):
        calls.append(jax.tree.leaves(tree)[0].shape)
        return tree

    for name, n_sites in [("dsgd", 1), ("gt", 2), ("dsgdm_n_sync", 2),
                          ("qhm", 0), ("gut", 2)]:
        calls.clear()
        n = 1 if name == "qhm" else 4
        opt = dc.replace(optim.make_optimizer(name, lr=LR),
                         mix_fn=counting_mix)
        p = toy_params(n)
        opt.step(p, toy_grad(p, 0), opt.init(p),
                 w=jnp.eye(n), lr=LR, t=0)
        assert len(calls) == n_sites, name


def test_mt_gut_commute_on_fixed_w_only():
    """Stage reordering is the whole difference between MT and GUT: with a
    FIXED W the beta- and W-power sums commute and the trajectories
    coincide; a time-varying topology breaks the commutation."""
    def run10(name, topo):
        opt = optim.make_optimizer(name, lr=LR)
        p = toy_params(topo.n)
        s = opt.init(p)
        for t in range(10):
            w = jnp.asarray(topo.w(t), jnp.float32)
            p, s = opt.step(p, toy_grad(p, t), s, w=w, lr=LR, t=t)
        return fingerprint(p)

    ring = topology.ring(8)
    a, b = run10("mt_dsgdm", ring), run10("gut", ring)
    np.testing.assert_allclose(a, b, rtol=1e-5)
    exp = topology.get_topology("exp", 8)
    a, b = run10("mt_dsgdm", exp), run10("gut", exp)
    assert abs(a[0] - b[0]) > 1e-4
