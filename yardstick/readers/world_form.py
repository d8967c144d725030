"""The parent's clock at launch to the last rank's stamp after
``hvd.init()`` returned: the launcher, the rendezvous, ``jax.distributed``
and the native core's bootstrap.  Only a multi-process cell has it."""


def read(ev, params):
    if "t_launch" not in ev:
        return None
    return max(ev["t_init"]) - ev["t_launch"]
