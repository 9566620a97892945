# `python -m mpisppy_tpu ...` == the generic_cylinders driver
# (ref:mpisppy/generic_cylinders.py run as a script).
from mpisppy_tpu.generic_cylinders import main
from mpisppy_tpu.utils.compile_cache import enable_compile_cache

enable_compile_cache()
main()
