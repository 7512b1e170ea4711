"""Test config: run everything on a virtual 8-device CPU mesh.

Must set env before jax initialises its backends — conftest is imported
before any test module, so this is the earliest reliable hook.
"""

import os

if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8"
                               ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# the persistent compilation cache is for the chip: on the CPU it would
# fill the checkout with XLA:CPU executables and log a machine-feature
# warning on every load
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import pytest  # noqa: E402


@pytest.fixture
def mesh8():
    """2x2x1x2 (data, fsdp, sequence, tensor) mesh on 8 CPU devices."""
    from fengshen_tpu.parallel import MeshConfig, make_mesh, set_mesh
    mesh = make_mesh(MeshConfig(data=2, fsdp=2, sequence=1, tensor=2))
    set_mesh(mesh)
    yield mesh
    set_mesh(None)


@pytest.fixture
def mesh_seq4():
    """1x1x4x2 mesh exercising sequence parallelism."""
    from fengshen_tpu.parallel import MeshConfig, make_mesh, set_mesh
    mesh = make_mesh(MeshConfig(data=1, fsdp=1, sequence=4, tensor=2))
    set_mesh(mesh)
    yield mesh
    set_mesh(None)


@pytest.fixture
def fresh_probe(monkeypatch):
    """The kernel tests' probe (tests/test_pallas_*.py): each force-env
    scenario re-probes; the cache key includes the env var so leaving it
    unset afterwards restores the real answer."""
    from fengshen_tpu.ops.pallas import FORCE_ENV, probe
    monkeypatch.delenv(FORCE_ENV, raising=False)
    yield monkeypatch
    probe(refresh=True)


@pytest.fixture
def latent_kernel_interpreted():
    """`with latent_kernel_interpreted() as took:` runs a model's
    full-form latent attention through the seam's Mosaic kernel in
    interpret mode, on the CPU: the seam's own decision with the
    probe's "no Mosaic here" taken out for this one op (a shape the
    kernel cannot tile still takes the walk; every other op keeps its
    xla lowering). `took`: the details of the call sites that took the
    kernel."""
    import contextlib
    import functools

    from fengshen_tpu.ops import pallas as kernels
    from fengshen_tpu.ops.pallas import latent_attention as kernel

    @contextlib.contextmanager
    def on():
        real, took = kernels.resolve_dispatch, []

        def resolve(op, detail, ineligible):
            if op == "mla_prefill_attention" and ineligible is None:
                took.append(detail)
                return "pallas"
            return real(op, detail, ineligible)
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(kernels, "resolve_dispatch", resolve)
            patch.setattr(
                kernel, "pallas_latent_prefill_attention",
                functools.partial(kernel.pallas_latent_prefill_attention,
                                  interpret=True))
            yield took
    return on


@pytest.fixture(autouse=True, scope="module")
def _no_mesh_left_behind():
    """`Trainer.__init__` (and a test that forgets) installs the
    process-global mesh and nothing takes it out. Under `--dist
    loadfile` the next file on the same worker would then build its
    serving engines under that mesh, where the first tick's fresh
    arrays and the later ticks' mesh-sharded outputs are two decode
    programs: the one-compile tests failed by file order."""
    yield
    import sys
    mesh_module = sys.modules.get("fengshen_tpu.parallel.mesh")
    if mesh_module is not None:
        mesh_module.set_mesh(None)
