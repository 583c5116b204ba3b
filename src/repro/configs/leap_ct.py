"""The paper's own workloads: projection geometries from Table 1 and the
limited-angle experiment (512^2 image, 720-view parallel beam)."""
from repro.core.geometry import VolumeGeometry, cone_beam, parallel_beam


def table1_parallel(n: int, n_angles: int):
    """Table-1 parallel beam: n^3 volume, n x 1.5n detector, 180 degrees."""
    return parallel_beam(n_angles, n, int(n * 1.5), VolumeGeometry(n, n, n),
                         angular_range=180.0)


def table1_cone(n: int, n_angles: int):
    """Table-1 cone beam: n^3 volume, n x 1.5n detector of 2 mm pixels,
    sod = 2n, sdd = 4n, full rotation."""
    return cone_beam(n_angles, n, int(n * 1.5), VolumeGeometry(n, n, n),
                     sod=2.0 * n, sdd=4.0 * n, pixel_width=2.0,
                     pixel_height=2.0, angular_range=360.0)


def table1_geometries(reduced: bool = False):
    """The four Table-1 cells: (parallel|cone) x (512^3/180 | 1024^3/720).
    ``reduced`` scales to CPU-runnable sizes, keeping aspect ratios."""
    cells = {}
    for n, na in ((512, 180), (1024, 720)):
        nn, nna = ((n // 8, na // 6) if n <= 512 else (n // 16, na // 12)) \
            if reduced else (n, na)
        cells[f"parallel_{n}_{na}"] = table1_parallel(nn, nna)
        cells[f"cone_{n}_{na}"] = table1_cone(nn, nna)
    return cells


def limited_angle_geometry(n: int = 512, n_angles: int = 720):
    vol = VolumeGeometry(n, n, 1)
    return parallel_beam(n_angles, 1, int(n * 1.5), vol, angular_range=180.0)
