from chipbench.tests.test_chipbench import *  # noqa: F401,F403 -- chipbench's own tests, collected by tier-1
