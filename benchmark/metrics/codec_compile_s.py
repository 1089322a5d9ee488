"""Codec and dispatch: seconds the program spent in its
``codec_compile`` stage, lowering and compiling the fused plan's
specialised kernel for the codec's registered matrix: the part of
``setup_s`` the codec's compile takes (``window_compiles`` says whether
a compile fell inside the window instead).  Read from the program's
plan stats; a program without the stage has nothing to read."""


def read(w):
    try:
        from ceph_tpu.ec import plan
    except ImportError:
        return None
    st = plan.stats()
    if st.get("codec_compiles", 0) <= 0:
        return None
    return float(st["codec_compile_s"])
