"""Host seconds of the program's front door, ``GraphSession`` built from
the host CSR, until its layout is resident: inside set-up."""


def read(run):
    return run.setup.get("layout_build_s")
