from .cli import main
from .utils.compile_cache import use_compile_cache

use_compile_cache()
main()
