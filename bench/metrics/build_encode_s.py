"""build_encode_s: seconds of the program's span ``vdms.build.encode`` (the SQ8
scale and int8 codes, in numpy) in the build that the window keeps for its
check, as ``VDMSInstance.build_seconds`` holds them."""


def read(ctx):
    seconds = getattr(ctx.log.kept, "build_seconds", None) or {}
    return seconds.get("build.encode")
