"""build_member_lists_s: seconds of the program's span
``vdms.build.member_lists`` (the per-segment member lists of the k-means
clusters, in numpy) in the build that the window keeps for its check, as
``VDMSInstance.build_seconds`` holds them."""


def read(ctx):
    seconds = getattr(ctx.log.kept, "build_seconds", None) or {}
    return seconds.get("build.member_lists")
