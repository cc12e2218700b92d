"""build_upload_s: seconds of the program's span ``vdms.build.upload`` (the host-
to-device copies of the segments and of the finished index arrays) in the
build that the window keeps for its check, as ``VDMSInstance.build_seconds``
holds them."""


def read(ctx):
    seconds = getattr(ctx.log.kept, "build_seconds", None) or {}
    return seconds.get("build.upload")
