"""The parallel and fan FBP backprojectors contract detector rows to z
slices once per reconstruction, after the view scan: the same volume as
the per-view formulation sum_a w_a S_a^T Lz, one dense contraction in the
program, and the counter ``fbp.z_contractions`` saying so (0 for FDK,
whose z sampling depends on the angle)."""
import ast
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import VolumeGeometry, cone_beam, fan_beam, parallel_beam
from repro.core.fbp import (_angle_weights, _fan_gamma, _lerp_matrix, fbp,
                            parker_weights)
from repro.core.filters import filter_sinogram
from repro.runtime import obs

# float32 rounding: the program sums a handful of float32 views, each a
# lerp of float32 samples at float32 detector coordinates; 1e-5 of the
# reference's largest magnitude is about 80 float32 ulps at that magnitude.
TOL = 1e-5

GEOMS = {
    # Lz the identity: rows and slices both 1 mm and centred.
    "parallel_identity": lambda: parallel_beam(
        7, 6, 20, VolumeGeometry(12, 10, 6)),
    # n_rows != nz, rows of 1.3 mm offset by half a pixel.
    "parallel_rows_ne_nz": lambda: parallel_beam(
        9, 5, 24, VolumeGeometry(12, 12, 7), pixel_height=1.3,
        center_row=0.5),
    "fan_flat": lambda: fan_beam(
        10, 5, 28, VolumeGeometry(12, 12, 4), sod=60.0, sdd=120.0,
        pixel_width=2.0, pixel_height=1.5, center_row=0.75),
    "fan_curved": lambda: fan_beam(
        11, 3, 30, VolumeGeometry(14, 12, 5), sod=70.0, sdd=130.0,
        pixel_width=2.0, center_row=-0.5, detector_type="curved"),
    "fan_flat_short_scan": lambda: fan_beam(
        12, 4, 28, VolumeGeometry(12, 12, 4), sod=60.0, sdd=120.0,
        pixel_width=2.0, angular_range=240.0, center_row=0.5),
}


def _lerp_cols(qa, ui):
    """Linear interpolation of each row of ``qa`` (nv, nu) at the fractional
    column indices ``ui`` (nxy,), zero outside the detector."""
    nu = qa.shape[1]
    j = np.floor(ui).astype(int)
    t = ui - j
    out = np.zeros((qa.shape[0], len(ui)))
    for jj, ww in ((j, 1 - t), (j + 1, t)):
        ok = (jj >= 0) & (jj < nu)
        out[:, ok] += qa[:, jj[ok]] * ww[ok]
    return out


def _per_view(g, sino):
    """sum_a w_a S_a^T Lz in float64, one dense contraction per view."""
    v = g.vol
    X = np.repeat(v.x_coords(), v.ny).astype(np.float64)
    Y = np.tile(v.y_coords(), v.nx).astype(np.float64)
    u0, du = float(g.u_coords()[0]), g.pixel_width
    Lz = _lerp_matrix(g.v_coords(), v.z_coords()).astype(np.float64)
    ang = np.asarray(g.angles_array(), np.float64)
    if g.geom_type == "parallel":
        q = filter_sinogram(sino, du)
        wts = _angle_weights(g.angles_array(), np.pi)
    else:
        sod, sdd = g.sod, g.sdd
        curved = g.detector_type == "curved"
        pre = sino * np.cos(_fan_gamma(g)).astype(np.float32)
        span = float(ang.max() - ang.min()) * (len(ang) / (len(ang) - 1))
        if span < 2.0 * np.pi * 0.99:
            pre = pre * parker_weights(g)[:, None, :]
            wts = _angle_weights(g.angles_array(), span)
        else:
            wts = _angle_weights(g.angles_array(), 2 * np.pi) / 2.0
        q = filter_sinogram(pre, du, equiangular_sdd=sdd if curved else 0.0)
        if not curved:
            q = q * (sdd / sod)
    q = np.asarray(q, np.float64)
    vol = np.zeros((v.nx * v.ny, v.nz))
    for a, (c, s) in enumerate(zip(np.cos(ang), np.sin(ang))):
        t = Y * c - X * s
        wdist = 1.0
        if g.geom_type == "fan":
            ell = sod - (X * c + Y * s)
            if curved:
                t = sdd * np.arctan2(t, ell)
                wdist = sod * sdd / (ell * ell + (Y * c - X * s) ** 2)
            else:
                t = sdd * t / ell
                wdist = sod ** 2 / (ell * ell)
        S = _lerp_cols(q[a], (t - u0) / du) * wdist
        vol += wts[a] * (S.T @ Lz)
    return vol.reshape(v.shape)


@pytest.mark.parametrize("name", list(GEOMS))
def test_hoisted_z_contraction_matches_per_view(name):
    g = GEOMS[name]()
    sino = jax.random.normal(jax.random.PRNGKey(3), g.sino_shape)
    if name == "parallel_rows_ne_nz" or name.startswith("fan"):
        Lz = _lerp_matrix(g.v_coords(), g.vol.z_coords())
        assert Lz.shape[0] != Lz.shape[1] or not np.array_equal(
            Lz, np.eye(len(Lz))), "Lz must not be the identity here"
    got = np.asarray(jax.jit(lambda p: fbp(p, g))(sino), np.float64)
    want = _per_view(g, sino)
    err = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert err < TOL, err


def _funcs(text):
    """The module's functions, name -> body lines."""
    out, name = {}, None
    for line in text.splitlines():
        m = re.match(r"\s*func\.func \w+ @([\w.$-]+)\(", line)
        if m:
            name = m.group(1)
            out[name] = []
        elif name is not None:
            out[name].append(line)
    return out


def _reach(lines, funcs):
    """Every line of ``lines`` and of the functions they call, transitively."""
    seen, todo, out = set(), [lines], []
    while todo:
        body = todo.pop()
        out += body
        for callee in re.findall(r"call @([\w.$-]+)\(", "\n".join(body)):
            if callee not in seen:
                seen.add(callee)
                todo.append(funcs[callee])
    return out


def _split_scan(main):
    """``main``'s lines inside the scan's while body, and the others."""
    for i, line in enumerate(main):
        if line.strip() == "} do {":
            indent = line[:len(line) - len(line.lstrip())]
            end = main.index(indent + "}", i + 1)
            return main[i + 1:end], main[:i] + main[end + 1:]
    raise AssertionError("no while loop in the program")


@pytest.mark.parametrize("kind,want", [("parallel", 1), ("fan", 1),
                                       ("cone", 0)])
def test_one_z_contraction_outside_the_scan(kind, want, monkeypatch):
    """``fbp.z_contractions`` reads 1 for parallel and flat fan and 0 for
    FDK after one traced call, and the lowered program has that many
    dot_generals, none of them inside the scan's while body.  Traced under
    a bfloat16 default, the contraction still runs at float32: it sums
    every view, so one bfloat16 pass would round the whole volume."""
    vol = VolumeGeometry(8, 8, 4)
    g = {"parallel": lambda: parallel_beam(6, 5, 12, vol, center_row=0.5),
         "fan": lambda: fan_beam(6, 5, 16, vol, sod=40.0, sdd=80.0),
         "cone": lambda: cone_beam(6, 5, 16, vol, sod=40.0, sdd=80.0)}[kind]()
    monkeypatch.setattr(obs, "_REC", obs.Recorder())
    with jax.default_matmul_precision("bfloat16"):
        text = jax.jit(lambda p: fbp(p, g)).lower(
            jax.ShapeDtypeStruct(g.sino_shape, jnp.float32)).as_text()
    assert obs.counter("fbp.z_contractions") == want
    funcs = _funcs(text)
    inside, outside = _split_scan(funcs["main"])
    assert not [ln for ln in _reach(inside, funcs) if "dot_general" in ln]
    dots = [ln for ln in _reach(outside, funcs) if "dot_general" in ln]
    assert len(dots) == want, dots
    for ln in dots:
        assert "precision = [HIGHEST, HIGHEST]" in ln, ln
    if kind != "parallel":
        return
    # The one contraction's second operand is the constant Lz.
    operand = re.search(r"dot_general %[\w#]+, (%\w+)", dots[0]).group(1)
    const = next(ln for ln in funcs["main"]
                 if ln.strip().startswith(operand + " = stablehlo.constant"))
    Lz = _lerp_matrix(g.v_coords(), vol.z_coords())
    dense = re.search(r"dense<(\[.*\])>", const).group(1)
    np.testing.assert_array_equal(np.asarray(ast.literal_eval(dense)), Lz)
    assert f"tensor<{Lz.shape[0]}x{Lz.shape[1]}xf32>" in const
