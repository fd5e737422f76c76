"""Loop drivers, chosen by a traffic file's ``driver`` key: ``train_fit``
(``Module.fit`` in a closed loop) and ``serve_ticks`` (``DecodeServer``
driven one ``serve_tick()`` at a time).  Each offers ``run(job) -> dict``.
"""
