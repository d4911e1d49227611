"""Serving: wall time of the program's ``serve.upload`` span (plan tables
and features to the device, inside dispatch; ``WaveStats.notes
["upload_ms"]``), over the window's waves. A program without the note
reads nothing."""


def read(ctx):
    ms = [w.notes["upload_ms"] for w in ctx["window"].waves
          if "upload_ms" in w.notes]
    return sum(ms) / len(ms) if ms else None
