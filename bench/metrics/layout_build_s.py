"""Host seconds of the program's ``build_slimsell`` and ``to_jax`` (until
the layout is resident) inside set-up."""


def read(run):
    return run.setup.get("layout_build_s")
