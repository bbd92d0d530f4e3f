# No eager submodule imports: import submodules explicitly, e.g.
# ``from ddl25spring_tpu.utils import pytree``.
